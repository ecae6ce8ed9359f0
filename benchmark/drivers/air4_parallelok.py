"""Driver of the deployed-contract cell: ``air4_dag``'s chain, set-up, block
loop and window (four in-process nodes, one batch in flight, every block of
the window through ``TransactionExecutor.dag_execute_transactions`` on each
replica) under calls to a Solidity contract and not to a registry precompile:
``ParallelOk.transfer(from, to, num)`` between accounts that exist, every
transaction carrying the DAG attribute, the contract's ABI declaring
``conflictFields`` on the two names. So every member of a level is bytecode on
the EVM engine, executed on the thread that executes the block, in index
order, in the batch's contract frame.

The driver sets nothing in the node (no ``FISCO_NO_NATIVE_EVM``, no
``FISCO_DAG_SERIAL``): the node's own rules choose the frame and the engine.

What differs from ``air4_dag``:

- set-up deploys the contract: the generator's ``create`` transaction carrying
  the ABI, committed alone through a served block ahead of the first opening
  batch; the receipt's address has to be the one the generator signed its
  calls to (the chain's rule for a created contract, worked out as an SDK
  would). The accounts are opened with ``set(name, balance)`` blocks,
  ``attribute`` 0, so they execute through the serial batch;
- the state-root buckets are warmed on rows of the contract's storage table
  (32-byte slot keys under ``/apps/<address>``);
- ``correct`` is decided against ``benchmark/refcontract.py``, a plain replay of
  the Solidity source that knows no EVM: all the accounts' balances read from
  each replica's storage rows at the reference's slots, a sample read back
  through the node's read-only ``balanceOf``, the sampled receipts, and
  ``calls_not_on_the_native_engine``: the replicas' executions of the window's
  transactions less the window's delta of
  ``fisco_executor_evm_calls_total{engine="native"}``, and the same delta of
  ``engine="interpreter"`` (``calls_the_python_interpreter_ran``). A block the
  Python interpreter quietly executed is then a wrong result and not a slow
  one. One more control reads the native counter one call short;
- the window's edges carry ``contract_counters.snapshot()``: the contract leg's
  readers (``contract_tx_us_per_tx``, ``evm_call_us_per_tx``,
  ``evm_native_call_share``, ``contract_framed_tx_share``) are entries of
  ``BENCHMARK.json`` and say all of it in a ``--trace 1`` line.

A checkout whose program does not say which engine finished a call cannot show
that: it leaves at once with the harness's "no program" code, before any chain
or compile."""

from __future__ import annotations

import random
import sys

from benchmark import contract_counters, refcontract
from benchmark.drivers import air4_dag

SAMPLE_CALLS = 256  # accounts read back through the read-only balanceOf


class Cell(air4_dag.Cell):
    def __init__(self, config: dict, traffic: dict, seed: int, spans):
        super().__init__(config, traffic, seed, spans)
        self.contract: bytes | None = None  # until set-up has deployed it
        self._slots: dict[str, bytes] = {}

    # -- set-up --------------------------------------------------------------

    def setup(self, seconds: float) -> None:
        from fisco_bcos_tpu.executor.evm import EVMResult

        if "engine" not in EVMResult.__dataclass_fields__:
            print("benchmark: the program in this checkout does not say which engine "
                  "finished an EVM call (fisco_executor_evm_calls_total)", file=sys.stderr)
            raise SystemExit(3)  # run.RC_NO_PROGRAM
        super().setup(seconds)

    def _commit(self, batch) -> list[int]:
        if self.contract is None:  # set-up's first block: the contract goes in ahead of it
            self._deploy()
        return super()._commit(batch)

    def _deploy(self) -> None:
        """The generator's ``create`` transaction through a served block of its
        own. The receipt's address is the one the corpus signed its calls to."""
        (self.deploy_status,) = super()._commit([self.corpus.deploy])
        ledger = self.nodes[0].ledger
        (tx_hash,) = ledger.tx_hashes_by_number(self.head())
        receipt = ledger.receipt_by_hash(tx_hash)
        if self.deploy_status != 0 or receipt is None or receipt.status != 0:
            raise RuntimeError(f"the contract was not deployed: {self.deploy_status} -> {receipt}")
        if bytes(receipt.contract_address) != self.corpus.contract:
            raise RuntimeError(
                f"the contract is at {bytes(receipt.contract_address).hex()}, and the corpus "
                f"signed its calls to {self.corpus.contract.hex()}")
        self.contract = self.corpus.contract

    def _warm_state_root_buckets(self) -> None:
        """``air4_dag``'s, on rows of the contract's own table: a transfer
        dirties two 32-byte slot rows and hot accounts repeat, so a block's
        state root is one hash batch over a few to 2,000 rows of this length.
        The rows go through the overlay's own hashing, so the shapes are the
        window's exactly."""
        from fisco_bcos_tpu.crypto.suite import ecdsa_suite
        from fisco_bcos_tpu.executor.evm import contract_table
        from fisco_bcos_tpu.ops.hash_common import bucket_ladder
        from fisco_bcos_tpu.storage.entry import Entry
        from fisco_bcos_tpu.storage.state_storage import StateStorage

        suite, table = ecdsa_suite(), contract_table(self.contract)
        for bucket in bucket_ladder(2 * self.batch_txs):
            rows = StateStorage(None)
            for i in range(bucket):
                rows.set_row(table, i.to_bytes(32, "big"), Entry().set(i.to_bytes(32, "big")))
            rows.hash(suite)

    # -- the window ----------------------------------------------------------

    def window(self, seconds: float) -> None:
        self.contract0 = contract_counters.snapshot()
        super().window(seconds)
        self.contract1 = contract_counters.snapshot()

    # -- correct -------------------------------------------------------------

    def slot(self, name: str) -> bytes:
        if name not in self._slots:
            self._slots[name] = refcontract.slot_of(name)
        return self._slots[name]

    def observe(self) -> dict:
        """``air4_dag``'s observation in plain values, with what a deployed
        contract shows in place of ``userBalance``: each replica's storage row
        at the reference's slot for every account (``balances``), what its
        read-only ``balanceOf`` answers for a sample of them (``calls``), and
        the window's deltas of the calls each engine finished."""
        from fisco_bcos_tpu.codec.abi import ABICodec
        from fisco_bcos_tpu.crypto.suite import ecdsa_suite
        from fisco_bcos_tpu.executor.evm import contract_table
        from fisco_bcos_tpu.protocol.transaction import TransactionFactory

        suite = ecdsa_suite()
        codec, fac = ABICodec(suite.hash), TransactionFactory(suite)
        table = contract_table(self.contract)
        rng = random.Random(self.seed ^ 0x5A3B1E)
        picks = [
            (k, rng.randrange(self.batch_txs))
            for k in rng.choices([k for k in self.offered if k != self.corrupt_k], k=air4_dag.SAMPLE_TXS)
        ]
        asked = {
            name: fac.create(
                chain_id="chain0", group_id="group0", block_limit=0, nonce="",
                to=self.contract, input=codec.encode_call("balanceOf(string)", name),
            )
            for name in rng.sample(self.corpus.names, min(SAMPLE_CALLS, len(self.corpus.names)))
        }
        tip = min(nd.block_number() for nd in self.nodes)
        served = self.nodes[0].ledger
        blocks = []
        for h in range(1, tip + 1):
            stored = [served.tx_by_hash(x) for x in served.tx_hashes_by_number(h)]
            blocks.append([b"" if tx is None else tx.encode() for tx in stored])
        replicas = []
        for nd in self.nodes:
            balances = {}
            for name in self.corpus.names:
                row = nd.storage.get_row(table, self.slot(name))
                balances[name] = 0 if row is None else int.from_bytes(row.get(), "big")
            calls = {}
            for name, call in asked.items():
                answer = nd.scheduler.call(call)
                calls[name] = (int(answer.status), bytes(answer.output))
            sample = []
            for k, i in picks:
                ack_hash = self.acks[k][i][1]
                stored, receipt = nd.ledger.tx_by_hash(ack_hash), nd.ledger.receipt_by_hash(ack_hash)
                sample.append(None if stored is None or receipt is None else {
                    "data": stored.encode_data(), "sig": bytes(stored.signature),
                    "wire": stored.encode(),
                    "status": int(receipt.status), "output": bytes(receipt.output),
                })
            replicas.append({
                "height": nd.block_number(),
                "state_roots": [
                    nd.ledger.header_by_number(h).state_root.hex() for h in range(1, tip + 1)],
                "committed": nd.ledger.total_transaction_count(),
                "balances": balances,
                "calls": calls,
                "sample": sample,
            })
        return {
            "replicas": replicas, "picks": picks, "blocks": blocks, "contract": self.contract,
            "acks": {k: list(v) for k, v in self.acks.items()},
            # the deploy is an acknowledged transaction of set-up like the opening's
            "opening_acks": [[self.deploy_status]] + [list(a) for a in self.opening_acks],
            "reruns": self.dag1["reruns"] - self.dag0["reruns"],
            "stage_lines": {h: list(v) for h, v in self.stage_lines.seen.items()
                            if h >= self.first_transfer_height},
            "window_heights": [s["height"] for s in self.series],
            "native_calls": contract_counters.window(self, "evm_native") or 0.0,
            "interpreter_calls": contract_counters.window(self, "evm_interpreter") or 0.0,
        }

    def compare(self, seen: dict) -> list[dict]:
        """``air4_dag``'s comparisons, the two that rest on the DagTransfer
        replay replaced by the contract's: every limit is 0."""
        balances, receipts = refcontract.replay(seen["blocks"], seen["contract"])
        receipt_of = {}  # wire bytes -> the replay's (status, output)
        for block, block_receipts in zip(seen["blocks"], receipts):
            receipt_of.update(zip(block, block_receipts))
        balance_off = call_off = receipt_off = 0
        for rep in seen["replicas"]:
            balance_off += sum(
                1 for name in self.corpus.names if rep["balances"].get(name) != balances.get(name, 0))
            call_off += sum(
                1 for name, answer in rep["calls"].items()
                if answer != (refcontract.OK, balances.get(name, 0).to_bytes(32, "big")))
            receipt_off += sum(
                1 for got in rep["sample"]
                if got is None or receipt_of.get(got["wire"]) != (got["status"], got["output"]))
        executions = len(seen["replicas"]) * self.window_blocks * self.batch_txs
        kept = [c for c in super().compare(seen) if c["name"] not in (
            "balances_differing_from_replay", "sampled_return_codes_differing_from_replay")]
        return kept + [
            {"name": "storage_balances_differing_from_replay", "value": balance_off, "limit": 0},
            {"name": "sampled_balanceOf_calls_differing_from_replay", "value": call_off, "limit": 0},
            {"name": "sampled_receipts_differing_from_replay", "value": receipt_off, "limit": 0},
            # a surplus is as wrong as a shortfall (a block counted twice), and
            # neither may cancel a call that the interpreter ran
            {"name": "calls_not_on_the_native_engine",
             "value": abs(int(executions - seen["native_calls"])), "limit": 0},
            {"name": "calls_the_python_interpreter_ran",
             "value": int(seen["interpreter_calls"]), "limit": 0},
        ]

    def controls(self) -> dict:
        """``air4_dag``'s five (``lost_write`` and ``lost_update`` act on the
        balances the storage rows gave) and the engine counter read one call
        short. ``correct`` has to come out false on each."""
        def one_call_short(seen):  # one call of the window ran in the Python interpreter
            seen["native_calls"] -= 1

        return dict(super().controls(), one_call_short=one_call_short)

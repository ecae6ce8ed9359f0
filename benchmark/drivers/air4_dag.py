"""Driver of the parallel-transfer cell: ``air4``'s chain and block loop
(``air4.Cell``: four in-process nodes, one batch in flight, submitted at the
next leader, gossiped, sealed and committed on all four) under blocks of
``userTransfer`` between accounts that exist, every transaction carrying the
DAG attribute, so each replica executes every block of the window through the
conflict-DAG runner (``TransactionExecutor.dag_execute_transactions``).

What differs from ``air4``:

- the generator is the configuration's (``config["generator"]``), not the mix's;
- set-up opens the accounts: ``user_batches`` blocks of ``userAdd`` committed
  through the chain before the two warm batches, both admission shapes (the
  opening's two-block payloads, the transfers' three-block ones) loaded while
  the corpus is signed, and every bucket a block's state-root hash batch can
  reach warmed (a transfer dirties two rows and hot accounts repeat, so the
  number of dirty rows changes from block to block);
- ``correct`` is decided against ``benchmark/reftransfer.py``: a plain replay
  of the bytes the chain stored, block by block in order.

The scheduler's stage line (``...|execute|...|dag=<n>|serial=<m>``, one a
block and replica on the ``scheduler`` logger) is read by a handler: every
transfer block has to read ``dag=<its size>|serial=0``."""

from __future__ import annotations

import importlib
import logging
import random
import re
import sys
import threading
import time

from benchmark import dag_counters, reftransfer
from benchmark.drivers import air4
from benchmark.drivers.air4 import WARM_BATCHES, _plain_admits
from benchmark.drivers.catchup import Chain

SAMPLE_TXS = 256  # committed transfers whose receipt, hash and sender are re-derived
TRACE_RING = 1 << 18  # span records: a level and its check leave one each
_STAGE_RE = re.compile(r"^\[ExecuteBlock\.(\d+)\.\d+\]\|execute\|.*\|dag=(\d+)\|serial=(\d+)$")


class StageLines(logging.Handler):
    """``height -> [(dag, serial), ...]``: one entry a replica that executed it."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.seen: dict[int, list[tuple[int, int]]] = {}

    def emit(self, record: logging.LogRecord) -> None:
        message = record.getMessage()
        m = _STAGE_RE.match(message) if "|execute|" in message else None
        if m:
            height, dag, serial = map(int, m.groups())
            self.seen.setdefault(height, []).append((dag, serial))


class Cell(air4.Cell):
    # -- set-up --------------------------------------------------------------

    def setup(self, seconds: float) -> None:
        from fisco_bcos_tpu.crypto import admission
        from fisco_bcos_tpu.observability import TRACER
        import numpy as np

        t = time.monotonic()
        self.nodes = Chain(self.config).nodes
        # a block leaves some 1,500 records more than a serial one (a span a
        # level and a check, four replicas): the ring has to hold the window
        TRACER.capacity = max(TRACER.capacity, TRACE_RING)
        self.stage_lines = StageLines()
        logging.getLogger("scheduler").addHandler(self.stage_lines)
        self.setup_parts["chain_s"] = time.monotonic() - t

        t = time.monotonic()
        offsets = air4.due_offsets(self.traffic, seconds)
        window_batches = (
            len(offsets) if offsets is not None else int(self.traffic["corpus_batches"])
        )
        # warm batches, the window's, the traced continuation's, the corrupted one
        total = WARM_BATCHES + window_batches + int(self.traffic["trace_blocks"]) + 1
        generator = importlib.import_module("benchmark.generators." + self.config["generator"])
        self.corpus = generator.Corpus(
            self.config, self.traffic, self.seed,
            block_limit=self.head() + int(self.config["block_limit_ahead"]),
        )
        self.corpus.sign_opening(1)
        self.corpus.sign_until(1)
        shapes = [
            ([tx.encode_data() for tx in batch],
             np.stack([np.frombuffer(tx.signature, np.uint8) for tx in batch]))
            for batch in (self.corpus.opening[0], self.corpus.batches[0])
        ]
        warm_error: list[BaseException] = []

        def warm_admission() -> None:
            # the public entry: the plane worker traces the cell's two
            # admission shapes and loads them from the compile cache while
            # this thread signs the corpus
            try:
                for payloads, sigs in shapes:
                    admission.admit_batch(payloads, sigs)
            except BaseException as e:  # re-raised on the main thread below
                warm_error.append(e)

        t_warm = time.monotonic()
        warm = threading.Thread(target=warm_admission, name="bench-warm-admission")
        warm.start()
        self.corpus.sign_opening()
        self.corpus.sign_until(total)
        self.setup_parts["corpus_s"] = time.monotonic() - t
        warm.join()
        if warm_error:
            raise warm_error[0]
        self.setup_parts["admission_program_s"] = time.monotonic() - t_warm

        t = time.monotonic()
        self.opening_acks = [self._commit(batch) for batch in self.corpus.opening]
        self.first_transfer_height = self.head() + 1
        self.setup_parts["open_accounts_s"] = time.monotonic() - t

        t = time.monotonic()
        self._warm_state_root_buckets()
        self.setup_parts["hash_buckets_s"] = time.monotonic() - t

        t = time.monotonic()
        for _ in range(WARM_BATCHES):
            self._block()
        self.setup_parts["warm_batches_s"] = time.monotonic() - t
        self.offsets = offsets

    def _commit(self, batch) -> list[int]:
        """One opening batch through the served path, outside the corpus's
        numbering. -> the entry node's acknowledgement statuses."""
        entry = self.leader_for(self.head() + 1)
        results = entry.txpool.submit_batch(batch)
        entry.tx_sync.maintain()
        self._commit_pool(entry)
        return [int(r.status) for r in results]

    def _warm_state_root_buckets(self) -> None:
        """A block's state root is one hash batch over its dirty rows: from a
        few (every transfer between the same two accounts) to two a
        transaction. Every bucket of that range is run once, on rows of the
        table's own length, so no block of the window meets a new shape."""
        from fisco_bcos_tpu.crypto.suite import ecdsa_suite
        from fisco_bcos_tpu.ops.hash_common import bucket_ladder

        suite = ecdsa_suite()
        row = b"\x0c" + b"dag_transfer" + (self.corpus.names[-1] + ":balance=999999").encode()
        for bucket in bucket_ladder(2 * self.batch_txs):
            suite.hash_batch_async([row + i.to_bytes(4, "big") for i in range(bucket)])()

    # -- the window ----------------------------------------------------------

    def window(self, seconds: float) -> None:
        self.dag0 = dag_counters.snapshot()
        super().window(seconds)
        self.dag1 = dag_counters.snapshot()

    # -- correct -------------------------------------------------------------

    def observe(self) -> dict:
        """What the system shows after the window, in plain values: each
        replica's height, committed count, state root at every height, the
        balance it reads back for every account and the sampled receipts and
        stored transactions; the first replica's blocks as wire bytes in
        chain order; the acknowledgements; the window's delta of the rerun
        counter; the scheduler's stage lines of the transfer blocks."""
        from fisco_bcos_tpu.codec.abi import ABICodec
        from fisco_bcos_tpu.crypto.suite import ecdsa_suite
        from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
        from fisco_bcos_tpu.protocol.transaction import TransactionFactory

        suite = ecdsa_suite()
        codec = ABICodec(suite.hash)
        fac = TransactionFactory(suite)
        calls = {
            u: fac.create(
                chain_id="chain0", group_id="group0", block_limit=0, nonce="",
                to=DAG_TRANSFER_ADDRESS, input=codec.encode_call("userBalance(string)", u),
            )
            for u in self.corpus.names
        }
        rng = random.Random(self.seed ^ 0x5A3B1E)
        picks = [
            (k, rng.randrange(self.batch_txs))
            for k in rng.choices([k for k in self.offered if k != self.corrupt_k], k=SAMPLE_TXS)
        ]
        tip = min(nd.block_number() for nd in self.nodes)
        served = self.nodes[0].ledger
        blocks = []
        for h in range(1, tip + 1):
            stored = [served.tx_by_hash(x) for x in served.tx_hashes_by_number(h)]
            blocks.append([b"" if tx is None else tx.encode() for tx in stored])
        replicas = []
        for nd in self.nodes:
            balances = {}
            for u, call in calls.items():
                code, bal = codec.decode_output(["uint256", "uint256"], nd.scheduler.call(call).output)
                balances[u] = bal if code == 0 else None
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
                "sample": sample,
            })
        return {
            "replicas": replicas, "picks": picks, "blocks": blocks,
            "acks": {k: list(v) for k, v in self.acks.items()},
            "opening_acks": [list(a) for a in self.opening_acks],
            "reruns": self.dag1["reruns"] - self.dag0["reruns"],
            "stage_lines": {h: list(v) for h, v in self.stage_lines.seen.items()
                            if h >= self.first_transfer_height},
            "window_heights": [s["height"] for s in self.series],
        }

    def compare(self, seen: dict) -> list[dict]:
        """The plain reference against what was observed. Every comparison is
        exact: the limit of each number is 0."""
        balances, codes = reftransfer.replay(seen["blocks"])
        code_of = {}  # wire bytes -> the replay's return code
        for block, block_codes in zip(seen["blocks"], codes):
            code_of.update(zip(block, block_codes))

        acked = sum(1 for acks in seen["opening_acks"] for s in acks if s == 0)
        unacked = sum(1 for acks in seen["opening_acks"] for s in acks if s != 0)
        for k in self.offered:
            broken = set(self.corrupt_lanes) if k == self.corrupt_k else set()
            for i, ack in enumerate(seen["acks"][k]):
                if i in broken:
                    continue
                acked += ack[0] == 0
                unacked += ack[0] != 0
        corrupt_accepted = sum(
            1 for i in self.corrupt_lanes if seen["acks"][self.corrupt_k][i][0] == 0
        )
        balance_off = uncommitted = sample_off = code_off = 0
        for rep in seen["replicas"]:
            uncommitted += abs(acked - rep["committed"])
            balance_off += sum(
                1 for user in self.corpus.names if rep["balances"].get(user) != balances.get(user))
            for (k, i), got in zip(seen["picks"], rep["sample"]):
                who = self.corpus.records[k][i][3]
                _status, ack_hash, ack_sender = seen["acks"][k][i]
                if got is None or not _plain_admits(
                    got["data"], got["sig"], self.corpus.secrets[who], ack_hash, ack_sender
                ):
                    sample_off += 1
                want = None if got is None else code_of.get(got["wire"])
                if (got is None or want is None or got["status"] != 0
                        or len(got["output"]) != 32
                        or int.from_bytes(got["output"], "big") != want):
                    code_off += 1
        heights = [rep["height"] for rep in seen["replicas"]]
        forked = sum(
            1 for roots in zip(*(rep["state_roots"] for rep in seen["replicas"]))
            if len(set(roots)) != 1
        )
        # every transfer block went to the DAG runner whole, on every replica;
        # a block of the window is one whole batch
        first = self.first_transfer_height
        sizes = {first + n: len(block) for n, block in enumerate(seen["blocks"][first - 1:])}
        not_dag = sum(
            1 for h, size in sizes.items()
            if len(seen["stage_lines"].get(h, ())) < len(seen["replicas"])
            or any(line != (size, 0) for line in seen["stage_lines"][h])
            or (h in seen["window_heights"] and size != self.batch_txs)
        )
        not_dag += sum(1 for h in seen["window_heights"] if h not in sizes)
        return [
            {"name": "valid_not_acknowledged", "value": unacked, "limit": 0},
            {"name": "acknowledged_not_committed", "value": uncommitted, "limit": 0},
            {"name": "balances_differing_from_replay", "value": balance_off, "limit": 0},
            {"name": "sampled_return_codes_differing_from_replay", "value": code_off, "limit": 0},
            {"name": "sampled_txs_differing_from_plain_crypto", "value": sample_off, "limit": 0},
            {"name": "corrupted_lanes_accepted", "value": corrupt_accepted, "limit": 0},
            {"name": "replica_height_spread", "value": max(heights) - min(heights), "limit": 0},
            {"name": "heights_with_more_than_one_state_root", "value": forked, "limit": 0},
            {"name": "dag_conflict_reruns_in_window", "value": seen["reruns"], "limit": 0},
            {"name": "transfer_blocks_not_all_dag", "value": not_dag, "limit": 0},
        ]

    def controls(self) -> dict:
        """``air4``'s four (the observation keeps their keys; a replica here
        holds a root a height, so ``forked_root`` is rewritten) and
        ``lost_update``. ``correct`` has to come out false on each."""
        def forked_root(seen):  # one replica on another state at the tip
            seen["replicas"][-1]["state_roots"][-1] = "00" * 32

        def lost_update(seen):
            # the hottest account short of one transfer that touched it on one
            # replica: what a level that ignored a dependency would leave
            rep = seen["replicas"][self.seed % len(seen["replicas"])]
            hot = self.corpus.names[0]
            amount = next(
                (amount for k in self.offered for payer, payee, amount, _who in self.corpus.records[k]
                 if hot in (payer, payee)), 1)
            rep["balances"][hot] = (rep["balances"][hot] or 0) + amount

        return {**super().controls(), "forked_root": forked_root, "lost_update": lost_update}

    def close(self) -> None:
        if hasattr(self, "stage_lines"):
            logging.getLogger("scheduler").removeHandler(self.stage_lines)
        split = {}
        for key in ("loop_ms", "levelize_ms", "run_ms", "validate_ms", "levels", "txs"):
            got = dag_counters.window(self, key)
            if got is not None:
                split[key] = round(got[0] / got[1], 3)
        print(f"dag counters, per block and replica over the window: {split}", file=sys.stderr)
        if hasattr(self, "nodes"):
            super().close()

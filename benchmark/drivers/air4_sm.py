"""Driver of the national-crypto chain cell: ``air4``'s chain, block loop,
window, traced block and corrupted block (``air4.Cell``: four in-process nodes
over ``InprocGateway`` sharing one DevicePlane, one batch in flight, submitted
at the next leader, gossiped, sealed and committed on all four) under the suite
an ``sm_crypto=true`` chain holds: SM2 + SM3, 128-byte signatures r ‖ s ‖ pub,
node keys on the SM2 curve, every hash, merkle root and state root SM3.

What differs from ``air4``:

- the chain is built with ``NodeConfig(sm_crypto=True)`` and the generator is
  the configuration's (``generators/sm_transfer_batches``); the admission shape
  is warmed through ``admit_batch(..., suite=sm_suite())``;
- the driver sets nothing in the node. Where a block's batch runs is the
  program's own rule (``device/dispatch.use_native_batch``): on the chip the
  fused SM program, four calls a block (the entry node's and, through the sync
  lane, the three replicas'). Set-up asks the rule once and says what it
  answered on standard error; ``correct`` holds every call of the window to it:
  ``admission_calls_not_on_the_device_leg`` (four times the window's blocks
  less the window's delta of
  ``fisco_device_dispatch_path_total{op="admission",path="device"}``) and
  ``sm_lanes_outside_the_fused_program`` (four times the window's transactions
  less the delta of ``fisco_device_items_total{op="admission_sm"}``), each as a
  distance. A batch the native loop answered for under the breaker, or one
  that went hash -> ZA -> e -> verify -> address as programs of their own, is
  then a wrong result and not a slow one. Off the chip (the CPU rehearsals)
  the rule keeps admission on the native loop unless
  ``FISCO_FORCE_DEVICE_ADMISSION`` pins the device leg, and then no device
  call is expected: a device call there is as wrong as a native one here;
- ``correct`` is decided against ``benchmark/refsm.py`` (plain SM2/SM3) where
  ``air4`` uses ``refcrypto.py``: ``SAMPLE_TXS`` committed transactions on each
  replica (hash, sender, and the carried key against the signer's), six
  corrupted lanes (``sm_signed_payloads.BROKEN``), and
  ``sampled_tx_roots_differing_from_plain_sm3``: the transactions root in the
  header of ``SAMPLE_ROOTS`` window blocks, on each replica, against
  ``benchmark/refsmroot.py`` over the plain SM3 of the bytes the replica
  stored. Balances are ``air4.compare``'s dict replay. Every limit is 0.

The window's edges carry ``sm_counters.snapshot()``. What the SM leg cost is
the manifest's to say (``device_leg_share.flood``, ``device_sync_ms_per_block.flood``,
``sm_admission_sync_ms_per_call``, ``merkle_fused_call_share`` in a ``--trace 1``
line). Every run still says once on standard error the whole counts no entry
carries and ``tests/benchmark_checks/test_sm_chain_cell.py`` holds: what each
op of the SM leg was given over the window, by the hasher its series names,
and its calls by leg (``sm leg, counts by op over the window: {...}``: every
merkle level under ``items_sm3``, no ``sm2_verify`` row, no
``admission_native`` row). No milliseconds: those are the entries'.

A checkout whose program has no fused SM admission leaves at once with the
harness's "no program" code, before any chain or compile."""

from __future__ import annotations

import functools
import importlib
import random
import sys
import threading
import time

from benchmark import refsm, refsmroot, sm_counters
from benchmark.drivers import air4
from benchmark.drivers.air4 import WARM_BATCHES

# committed transactions re-derived by the plain reference: 256 take some 4 s
# of plain Python (two scalar multiplications each, 15 ms), and the eight roots
# some 6 s (a thousand plain SM3 a block): inside the 30 s the cell gives them
SAMPLE_TXS = 256
SAMPLE_ROOTS = 8  # window blocks whose transactions root is rebuilt in plain SM3


@functools.lru_cache(maxsize=None)
def _pubkey(secret: int) -> bytes:
    return refsm.pubkey_bytes(secret)


@functools.lru_cache(maxsize=None)
def _plain_admits(data: bytes, sig: bytes, secret: int, ack_hash: bytes, ack_sender: bytes) -> bool:
    """What the node acknowledged (hash, sender) is what plain SM3 and SM2
    give for the stored payload and signature, and the key the signature
    carries is the signer's. Cached: four replicas hold the same bytes."""
    ok, sender, pub, digest = refsm.admit(data, sig)
    return ok and digest == ack_hash and sender == ack_sender and pub == _pubkey(secret)


@functools.lru_cache(maxsize=4 * SAMPLE_ROOTS)
def _plain_root(payloads: tuple) -> bytes:
    return refsmroot.txs_root(list(payloads))


class Cell(air4.Cell):
    # -- set-up --------------------------------------------------------------

    def setup(self, seconds: float) -> None:
        """``air4``'s set-up under the SM suite (its chain, corpus, admission
        warm-up beside the signing, two warm batches)."""
        import jax
        import numpy as np

        from fisco_bcos_tpu.crypto import admission
        from fisco_bcos_tpu.crypto.suite import sm_suite
        from fisco_bcos_tpu.device.dispatch import use_native_batch
        from fisco_bcos_tpu.front import InprocGateway
        from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig
        from fisco_bcos_tpu.node import Node, NodeConfig

        sm = sm_suite()
        if getattr(sm, "fused_admission", lambda: None)() is None:
            print("benchmark: the program in this checkout has no fused SM2/SM3 "
                  "admission (CryptoSuite.fused_admission)", file=sys.stderr)
            raise SystemExit(3)  # run.RC_NO_PROGRAM
        platform = jax.default_backend()
        self.device_leg = platform == "tpu" or not use_native_batch(self.batch_txs, "admission")
        print(f"sm leg: on {platform} the program's rule sends a batch of {self.batch_txs} to the "
              f"{'fused SM program' if self.device_leg else 'native loop'}: "
              f"{4 if self.device_leg else 0} device calls a block expected", file=sys.stderr)

        t = time.monotonic()
        replicas = int(self.config["replicas"])
        keypairs = [sm.signature_impl.generate_keypair(secret=0x5C41B + i) for i in range(replicas)]
        committee = [ConsensusNode(kp.pub, weight=1) for kp in keypairs]
        gw = InprocGateway(auto=True)
        self.nodes = []
        for kp in keypairs:
            cfg = NodeConfig(sm_crypto=True, genesis=GenesisConfig(
                consensus_nodes=list(committee),
                tx_count_limit=int(self.config["tx_count_limit"]),
            ))
            node = Node(cfg, keypair=kp)
            gw.connect(node.front)
            self.nodes.append(node)
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
            self.traffic, self.seed,
            block_limit=self.head() + int(self.config["block_limit_ahead"]),
        )
        self.corpus.sign_until(1)
        first = self.corpus.batches[0]
        payloads = [tx.encode_data() for tx in first]
        sigs = np.stack([np.frombuffer(tx.signature, np.uint8) for tx in first])
        warm_error: list[BaseException] = []

        def warm_admission() -> None:
            # the public entry: the plane worker traces the cell's one
            # admission shape and loads it from the compile cache while this
            # thread signs the corpus
            try:
                admission.admit_batch(payloads, sigs, suite=sm)
            except BaseException as e:  # re-raised on the main thread below
                warm_error.append(e)

        t_warm = time.monotonic()
        warm = threading.Thread(target=warm_admission, name="bench-warm-admission")
        warm.start()
        self.corpus.sign_until(total)
        self.setup_parts["corpus_s"] = time.monotonic() - t
        warm.join()
        if warm_error:
            raise warm_error[0]
        self.setup_parts["admission_program_s"] = time.monotonic() - t_warm

        t = time.monotonic()
        for _ in range(WARM_BATCHES):
            self._block()
        self.setup_parts["warm_batches_s"] = time.monotonic() - t
        self.offsets = offsets

    # -- the window ----------------------------------------------------------

    def window(self, seconds: float) -> None:
        self.sm0 = sm_counters.snapshot()
        super().window(seconds)
        self.sm1 = sm_counters.snapshot()

    # -- correct -------------------------------------------------------------

    def observe(self) -> dict:
        """``air4``'s observation in plain values, read under the SM codec
        (selectors are SM3's first four bytes), with a larger sample of
        committed transactions, the header's transactions root and the stored
        payloads, in block order, of the sampled window blocks on each
        replica, and the window's deltas of the device leg's two counters."""
        from fisco_bcos_tpu.codec.abi import ABICodec
        from fisco_bcos_tpu.crypto.suite import sm_suite
        from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
        from fisco_bcos_tpu.protocol.transaction import TransactionFactory

        suite = sm_suite()
        codec = ABICodec(suite.hash)
        fac = TransactionFactory(suite)
        users = [rec[0] for k in self.offered for rec in self.corpus.records[k]]
        calls = {
            u: fac.create(
                chain_id="chain0", group_id="group0", block_limit=0, nonce="",
                to=DAG_TRANSFER_ADDRESS, input=codec.encode_call("userBalance(string)", u),
            )
            for u in users
        }
        rng = random.Random(self.seed ^ 0x5A3B1E)
        picks = [
            (k, rng.randrange(self.batch_txs))
            for k in rng.choices([k for k in self.offered if k != self.corrupt_k], k=SAMPLE_TXS)
        ]
        heights = [s["height"] for s in self.series]
        rooted = sorted(rng.sample(heights, min(SAMPLE_ROOTS, len(heights))))
        replicas = []
        for nd in self.nodes:
            balances = {}
            for u, call in calls.items():
                code, bal = codec.decode_output(["uint256", "uint256"], nd.scheduler.call(call).output)
                balances[u] = bal if code == 0 else None
            sample = []
            for k, i in picks:
                stored = nd.ledger.tx_by_hash(self.acks[k][i][1])
                sample.append(None if stored is None else {
                    "data": stored.encode_data(), "sig": bytes(stored.signature),
                })
            roots = {}
            for h in rooted:
                stored = [nd.ledger.tx_by_hash(x) for x in nd.ledger.tx_hashes_by_number(h)]
                roots[h] = {
                    "root": bytes(nd.ledger.header_by_number(h).txs_root),
                    "payloads": tuple(b"" if tx is None else tx.encode_data() for tx in stored),
                }
            n = nd.block_number()
            replicas.append({
                "height": n,
                "state_root": nd.ledger.header_by_number(n).state_root.hex(),
                "committed": nd.ledger.total_transaction_count(),
                "balances": balances,
                "sample": sample,
                "roots": roots,
                "block_sizes": {
                    s["height"]: len(nd.ledger.tx_hashes_by_number(s["height"]))
                    for s in self.series
                },
            })
        return {"replicas": replicas, "picks": picks,
                "acks": {k: list(v) for k, v in self.acks.items()},
                "device_calls": sm_counters.window(self, "admission", "calls_device"),
                "fused_lanes": sm_counters.window(self, "admission_sm", "items")}

    def compare(self, seen: dict) -> list[dict]:
        """``air4``'s eight numbers, the sample re-derived through plain
        SM2/SM3, and the SM leg's three. Every limit is 0."""
        sample_off = root_off = 0
        for rep in seen["replicas"]:
            for (k, i), got in zip(seen["picks"], rep["sample"]):
                who = self.corpus.records[k][i][2]
                _status, ack_hash, ack_sender = seen["acks"][k][i]
                if got is None or not _plain_admits(
                    got["data"], got["sig"], self.corpus.secrets[who], ack_hash, ack_sender
                ):
                    sample_off += 1
            for got in rep["roots"].values():
                if not all(got["payloads"]) or got["root"] != _plain_root(got["payloads"]):
                    root_off += 1
        # air4's own loop over the sample is refcrypto's: it is given none
        out = super().compare(dict(seen, picks=()))
        for c in out:
            if c["name"] == "sampled_txs_differing_from_plain_crypto":
                c["value"] = sample_off
        calls = len(seen["replicas"]) * self.window_blocks if self.device_leg else 0
        return out + [
            # a surplus is as wrong as a shortfall: a distance, not a difference
            {"name": "admission_calls_not_on_the_device_leg",
             "value": abs(int(calls - seen["device_calls"])), "limit": 0},
            {"name": "sm_lanes_outside_the_fused_program",
             "value": abs(int(calls * self.batch_txs - seen["fused_lanes"])), "limit": 0},
            {"name": "sampled_tx_roots_differing_from_plain_sm3", "value": root_off, "limit": 0},
        ]

    def controls(self) -> dict:
        """``air4``'s four and the SM leg's four. ``correct`` has to come
        out false on each."""
        def accepted_neighbours_key(seen):  # the lane carrying the next sender's key acknowledged
            lane = self.corrupt_lanes[5]
            seen["acks"][self.corrupt_k][lane] = (0,) + seen["acks"][self.corrupt_k][lane][1:]

        def one_call_short(seen):  # one batch of the window did not take the device leg
            seen["device_calls"] -= 1

        def one_batch_not_fused(seen):  # a device call whose lanes ran as programs of their own
            seen["fused_lanes"] -= self.batch_txs

        def flipped_root(seen):  # one sampled header's transactions root off by a byte
            rep = seen["replicas"][self.seed % len(seen["replicas"])]
            got = rep["roots"][sorted(rep["roots"])[self.seed % len(rep["roots"])]]
            got["root"] = bytes([got["root"][0] ^ 0x01]) + got["root"][1:]

        return dict(super().controls(), accepted_neighbours_key=accepted_neighbours_key,
                    one_call_short=one_call_short, one_batch_not_fused=one_batch_not_fused,
                    flipped_root=flipped_root)

    def close(self) -> None:
        if hasattr(self, "sm1"):
            print(f"sm leg, counts by op over the window: {self._sm_counts()}", file=sys.stderr)
        if hasattr(self, "nodes"):
            super().close()

    def _sm_counts(self) -> dict:
        """The window's deltas of the SM leg's whole counts by op: the items it
        was given (by hasher where the series names one) and its calls by leg.
        A count that did not move, and an op none of whose did, is left out."""
        out: dict = {"blocks": self.window_blocks}
        for op, after in sorted(self.sm1.items()):
            moved = {key: sm_counters.window(self, op, key)
                     for key in after if key.startswith(("items", "calls_"))}
            moved = {k: v for k, v in moved.items() if v}
            if moved:
                out[op] = moved
        return out

"""Driver of the chain cells: BASELINE config #4 in its one-chip mapping.
Four in-process nodes over ``InprocGateway`` share one DevicePlane; engines
are driven inline on this thread. The chain construction and the block loop
are copied from ``chip_smoke.py:child_air4`` (a proof, not a measurement) and
changed only where a measurement needs it: a schedule, spans, a series.

One batch is in flight at a time: the oldest due batch is submitted at the
next leader (fused admission on the device), gossiped (the three replicas
admit on the sync lane), sealed and committed on all four; then the next due
batch is taken, or the loop sleeps to its due time."""

from __future__ import annotations

import functools
import random
import threading
import time

from benchmark import refcrypto
from benchmark.generators.transfer_batches import Corpus, due_offsets

WARM_BATCHES = 2  # of the cell's own size, so every shape is resident
SAMPLE_TXS = 24  # committed transactions re-derived by the plain reference
STALL_S = 60.0


@functools.lru_cache(maxsize=None)
def _plain_admits(data: bytes, sig: bytes, secret: int, ack_hash: bytes, ack_sender: bytes) -> bool:
    """What the node acknowledged (hash, sender) is what plain keccak256 and
    secp256k1 give for the stored payload, signature and the signer's key.
    Cached: four replicas hold the same bytes."""
    pub = refcrypto.pubkey_bytes(secret)
    return (
        ack_hash == refcrypto.keccak256(data)
        and refcrypto.verify(ack_hash, sig, pub)
        and ack_sender == refcrypto.address(pub)
    )


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, spans):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.spans = spans
        self.batch_txs = int(traffic["batch_txs"])
        self.lanes_per_block = self.batch_txs * int(config["replicas"])
        self.series: list[dict] = []
        self.offered: list[int] = []  # corpus batches submitted, in order
        self.attempted = 0
        self.setup_parts: dict[str, float] = {}
        self.next_batch = 0
        self.corrupt_lanes: list[int] = []
        self.acks: dict[int, list[tuple[int, bytes, bytes]]] = {}  # batch -> (status, hash, sender)

    # -- set-up --------------------------------------------------------------

    def setup(self, seconds: float) -> None:
        from fisco_bcos_tpu.crypto import admission
        from fisco_bcos_tpu.front import InprocGateway
        from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig
        from fisco_bcos_tpu.node import Node, NodeConfig
        from fisco_bcos_tpu.crypto.suite import ecdsa_suite
        import numpy as np

        t = time.monotonic()
        sign = ecdsa_suite().signature_impl
        replicas = int(self.config["replicas"])
        keypairs = [sign.generate_keypair(secret=0xC41B + i) for i in range(replicas)]
        committee = [ConsensusNode(kp.pub, weight=1) for kp in keypairs]
        gw = InprocGateway(auto=True)
        self.nodes = []
        for kp in keypairs:
            cfg = NodeConfig(genesis=GenesisConfig(
                consensus_nodes=list(committee),
                tx_count_limit=int(self.config["tx_count_limit"]),
            ))
            node = Node(cfg, keypair=kp)
            gw.connect(node.front)
            self.nodes.append(node)
        self.setup_parts["chain_s"] = time.monotonic() - t

        t = time.monotonic()
        offsets = due_offsets(self.traffic, seconds)
        window_batches = (
            len(offsets) if offsets is not None else int(self.traffic["corpus_batches"])
        )
        # warm batches, the window's, the traced continuation's, the corrupted one
        total = WARM_BATCHES + window_batches + int(self.traffic["trace_blocks"]) + 1
        self.corpus = Corpus(
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
                admission.admit_batch(payloads, sigs)
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

    # -- the block path (chip_smoke.py's loop) -------------------------------

    def head(self) -> int:
        return max(nd.engine.consensus_head()[0] for nd in self.nodes)

    def leader_for(self, height: int):
        cfg = self.nodes[0].pbft_config
        target = cfg.nodes[cfg.leader_index(height, 0)].node_id
        return next(nd for nd in self.nodes if nd.node_id == target)

    def _commit_pool(self, entry) -> None:
        """Seal at whichever node leads until ``entry``'s pool is empty and
        every replica holds the tip."""
        last_head, last_progress = self.head(), time.monotonic()
        while entry.txpool.pending_count() > 0:
            now, h = time.monotonic(), self.head()
            if h != last_head:
                last_head, last_progress = h, now
            elif now - last_progress > STALL_S:
                raise RuntimeError(f"chain stalled at height {h}")
            if not self.leader_for(h + 1).sealer.seal_and_submit():
                time.sleep(0.002)
        for nd in self.nodes:
            nd.scheduler.drain_commits(60.0)
        tip = max(nd.block_number() for nd in self.nodes)
        deadline = time.monotonic() + 30.0
        while any(nd.block_number() < tip for nd in self.nodes):
            if time.monotonic() > deadline:
                raise RuntimeError(f"replicas did not converge on height {tip}")
            time.sleep(0.002)

    def _block(self) -> float:
        """Submit the next corpus batch at the next leader, gossip, seal and
        commit it on all replicas. -> seconds of the admission span."""
        k = self.next_batch
        self.next_batch += 1
        batch = self.corpus.batches[k]
        entry = self.leader_for(self.head() + 1)
        with self.spans.span("bench.submit_batch"):
            results = entry.txpool.submit_batch(batch)
            entry.tx_sync.maintain()
        _name, t_admit0, t_admit1 = self.spans.rows[-1]
        with self.spans.span("bench.seal_and_submit"):
            self._commit_pool(entry)
        self.offered.append(k)
        self.acks[k] = [(int(r.status), bytes(r.tx_hash), bytes(r.sender)) for r in results]
        return t_admit1 - t_admit0

    # -- the window ----------------------------------------------------------

    def _run(self, t0: float, offsets, seconds: float, blocks: int | None, keep: int) -> None:
        """Drive batches from ``t0``: on the schedule ``offsets`` where the mix
        has one, else back to back until ``seconds`` have passed or ``blocks``
        are done; ``keep`` corpus batches are left for what follows."""
        k = 0
        while self.next_batch < len(self.corpus.batches) - keep:
            if offsets is not None:
                if k >= len(offsets):
                    break
                due = t0 + offsets[k]
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
            else:
                if blocks is not None and k >= blocks:
                    break
                if blocks is None and time.perf_counter() - t0 >= seconds:
                    break
                due = time.perf_counter()
            t_sub = time.perf_counter()
            height = self.head() + 1
            admit_s = self._block()
            t_commit = time.perf_counter()
            self.series.append({
                "k": k, "due_s": round(due - t0, 4), "late_ms": (t_sub - due) * 1e3,
                "commit_ms": (t_commit - due) * 1e3,
                "block_ms": (t_commit - t_sub) * 1e3, "admit_ms": admit_s * 1e3,
                "height": height,
            })
            k += 1

    def window(self, seconds: float) -> None:
        self.committed0 = min(nd.ledger.total_transaction_count() for nd in self.nodes)
        self.t0 = time.perf_counter()
        # kept back: the traced continuation's batches and the corrupted one
        self._run(self.t0, self.offsets, seconds, None, int(self.traffic["trace_blocks"]) + 1)
        self.t1 = time.perf_counter()
        self.committed1 = min(nd.ledger.total_transaction_count() for nd in self.nodes)
        self.window_blocks = len(self.series)
        self.attempted = self.window_blocks * self.batch_txs

    def traced(self, blocks: int) -> None:
        """The same cadence for ``blocks`` more blocks, under the profiler."""
        tick = float(self.traffic["tick_s"])
        offsets = [k * tick for k in range(blocks)] if tick > 0 else None
        n = len(self.series)
        t0 = time.perf_counter()
        self._run(t0, offsets, 0.0, blocks, 1)
        if tick > 0:  # whole ticks, so the pacing shows in the idle share
            time.sleep(max(0.0, t0 + blocks * tick - time.perf_counter()))
        self.traced_series, self.series = self.series[n:], self.series[:n]

    def end_to_end(self) -> dict:
        from statistics import median

        elapsed = self.t1 - self.t0
        return {
            "committed_tps": (self.committed1 - self.committed0) / elapsed,
            "commit_p50_ms": median(s["commit_ms"] for s in self.series),
        }

    # -- correct -------------------------------------------------------------

    def after_window(self) -> None:
        """One more batch with corrupted lanes: they must be rejected, on
        exactly those lanes, and never committed."""
        self.corrupt_k = len(self.corpus.batches) - 1
        self.next_batch = self.corrupt_k
        self.corrupt_lanes = self.corpus.corrupt(self.corrupt_k)
        self._block()

    def observe(self) -> dict:
        """What the system shows after the window, in plain values: each
        replica's height, state root, committed count and the balance it reads
        back for every user offered; the sample of committed transactions as
        each replica's ledger holds them."""
        from fisco_bcos_tpu.codec.abi import ABICodec
        from fisco_bcos_tpu.crypto.suite import ecdsa_suite
        from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
        from fisco_bcos_tpu.protocol.transaction import TransactionFactory

        suite = ecdsa_suite()
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
            n = nd.block_number()
            replicas.append({
                "height": n,
                "state_root": nd.ledger.header_by_number(n).state_root.hex(),
                "committed": nd.ledger.total_transaction_count(),
                "balances": balances,
                "sample": sample,
                "block_sizes": {
                    s["height"]: len(nd.ledger.tx_hashes_by_number(s["height"]))
                    for s in self.series
                },
            })
        return {"replicas": replicas, "picks": picks,
                "acks": {k: list(v) for k, v in self.acks.items()}}

    def compare(self, seen: dict) -> list[dict]:
        """The plain reference against what was observed. Every comparison is
        exact: the limit of each number is 0."""
        balances: dict[str, int] = {}
        acked = unacked = 0
        for k in self.offered:
            broken = set(self.corrupt_lanes) if k == self.corrupt_k else set()
            for i, (user, amount, _who) in enumerate(self.corpus.records[k]):
                if i in broken:
                    continue
                if seen["acks"][k][i][0] == 0:
                    acked += 1
                    balances.setdefault(user, amount)  # userAdd: first write wins
                else:
                    unacked += 1
        corrupt_accepted = sum(
            1 for i in self.corrupt_lanes if seen["acks"][self.corrupt_k][i][0] == 0
        )
        balance_off = uncommitted = sample_off = size_off = 0
        corrupt_users = {self.corpus.records[self.corrupt_k][i][0] for i in self.corrupt_lanes}
        for rep in seen["replicas"]:
            uncommitted += abs(acked - rep["committed"])
            for user, got in rep["balances"].items():
                want = balances.get(user)
                if got != want:
                    if user in corrupt_users and want is None:
                        corrupt_accepted += 1
                    else:
                        balance_off += 1
            for (k, i), got in zip(seen["picks"], rep["sample"]):
                who = self.corpus.records[k][i][2]
                _status, ack_hash, ack_sender = seen["acks"][k][i]
                if got is None or not _plain_admits(
                    got["data"], got["sig"], self.corpus.secrets[who], ack_hash, ack_sender
                ):
                    sample_off += 1
            size_off += sum(1 for n in rep["block_sizes"].values() if n != self.batch_txs)
        heights = [rep["height"] for rep in seen["replicas"]]
        return [
            {"name": "valid_not_acknowledged", "value": unacked, "limit": 0},
            {"name": "acknowledged_not_committed", "value": uncommitted, "limit": 0},
            {"name": "balances_differing_from_replay", "value": balance_off, "limit": 0},
            {"name": "sampled_txs_differing_from_plain_crypto", "value": sample_off, "limit": 0},
            {"name": "corrupted_lanes_accepted", "value": corrupt_accepted, "limit": 0},
            {"name": "replica_height_spread", "value": max(heights) - min(heights), "limit": 0},
            {"name": "state_roots_beyond_one",
             "value": len({rep["state_root"] for rep in seen["replicas"]}) - 1, "limit": 0},
            {"name": "window_blocks_not_one_batch", "value": size_off, "limit": 0},
        ]

    def controls(self) -> dict:
        """Degraded variants of the observation, each breaking one guarantee
        the configuration states. ``correct`` has to come out false on each."""
        def lost_write(seen):  # an acknowledged write missing on one replica
            rep = seen["replicas"][self.seed % len(seen["replicas"])]
            user = sorted(rep["balances"])[self.seed % len(rep["balances"])]
            rep["balances"][user] = None

        def forked_root(seen):  # one replica on another state
            seen["replicas"][-1]["state_root"] = "00" * 32

        def truncated_digest(seen):  # admission answering with 16-byte digests
            for k, i in seen["picks"]:
                status, h, sender = seen["acks"][k][i]
                seen["acks"][k][i] = (status, h[:16] + bytes(16), sender)

        def accepted_corrupt(seen):  # a lane with r = 0 acknowledged
            lane = self.corrupt_lanes[0]
            seen["acks"][self.corrupt_k][lane] = (0,) + seen["acks"][self.corrupt_k][lane][1:]

        return {
            "lost_write": lost_write, "forked_root": forked_root,
            "truncated_digest": truncated_digest, "accepted_corrupt": accepted_corrupt,
        }

    def failed_count(self) -> int:
        return sum(
            1 for k in self.offered[WARM_BATCHES:WARM_BATCHES + self.window_blocks]
            for s in self.acks[k] if s[0] != 0
        )

    def close(self) -> None:
        for nd in self.nodes:
            nd.stop()

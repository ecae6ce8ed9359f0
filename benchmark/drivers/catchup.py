"""Driver of the catch-up cell: a 4-node Air chain (``air4``'s construction)
in which one replica was away while the other three committed a backlog of
full blocks. The window is the replica's way back: it reconnects, hears a
live peer's status and catches up through the node's own ``BlockSync`` over
``InprocGateway`` — ranges of 32 blocks asked for one after another, every
transaction of the downloaded blocks re-verified on the device in gathers of
whole blocks up to 10,240 lanes, each block executed and committed — until it
stands at the chain's head (or ``--seconds`` are over: its requests are then
dropped, and it finishes outside the window).

Set-up builds the backlog on the three live replicas (the absent node's turns
to lead are timed out into the next view) and, on a second chain of the same
construction, the ``trace_blocks`` the profiler watches after the window: one
more gather, with nothing else of the process under the profiler.

A checkout whose block sync does not re-verify (no ``VERIFY_LANES_MAX``)
leaves at once with the harness's "no program" code."""

from __future__ import annotations

import functools
import random
import sys
import threading
import time

from benchmark import refcrypto, refsync, sync_counters
from benchmark.drivers.air4 import STALL_S, WARM_BATCHES
from benchmark.generators.transfer_batches import SECP_N, Corpus

SAMPLE_TXS = 256  # synced transactions re-derived by the plain reference
AWAY = 3  # the node of each chain that goes away

_pubkey = functools.lru_cache(maxsize=None)(refcrypto.pubkey_bytes)  # 64 signers, 256 samples


@functools.lru_cache(maxsize=None)
def _plain_admits(data: bytes, sig: bytes, secret: int, held_hash: bytes, held_sender: bytes) -> bool:
    """The hash and sender the replica executed a transaction with are what
    plain keccak256 and secp256k1 give for the bytes it stored and the
    signer's key. Cached: the controls compare one observation five times."""
    pub = _pubkey(secret)
    return (
        held_hash == refcrypto.keccak256(data)
        and refcrypto.verify(held_hash, sig, pub)
        and held_sender == refcrypto.address(pub)
    )


class Chain:
    """Four in-process nodes of one committee over one gateway; ``away`` is
    the replica that is disconnected while ``live`` go on."""

    def __init__(self, config: dict):
        from fisco_bcos_tpu.crypto.suite import ecdsa_suite
        from fisco_bcos_tpu.front import InprocGateway
        from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig
        from fisco_bcos_tpu.node import Node, NodeConfig

        sign = ecdsa_suite().signature_impl
        keypairs = [sign.generate_keypair(secret=0xC41B + i) for i in range(int(config["replicas"]))]
        committee = [ConsensusNode(kp.pub, weight=1) for kp in keypairs]
        self.gw = InprocGateway(auto=True)
        self.nodes = []
        for kp in keypairs:
            cfg = NodeConfig(genesis=GenesisConfig(
                consensus_nodes=list(committee),
                tx_count_limit=int(config["tx_count_limit"]),
            ))
            node = Node(cfg, keypair=kp)
            self.gw.connect(node.front)
            self.nodes.append(node)
        self.away = self.nodes[AWAY]
        self.live = [nd for nd in self.nodes if nd is not self.away]
        self.present = list(self.nodes)

    def disconnect(self) -> None:
        self.gw.disconnect(self.away.node_id)
        self.present = list(self.live)

    def head(self) -> int:
        return max(nd.engine.consensus_head()[0] for nd in self.present)

    def leader_for(self, height: int):
        """Who of the present nodes leads ``height``; a turn of the absent
        one is timed out by the others into the next view."""
        cfg = self.live[0].pbft_config
        for _ in range(len(cfg.nodes)):
            target = cfg.nodes[cfg.leader_index(height, self.live[0].engine.view)].node_id
            leader = next((nd for nd in self.present if nd.node_id == target), None)
            if leader is not None:
                return leader
            for nd in self.present:
                nd.engine.on_timeout()
        raise RuntimeError(f"no present leader for height {height}")

    def commit(self, batch) -> list:
        """One batch through the served path of the present nodes (``air4``'s
        loop): submitted at the leader, gossiped, sealed, committed on all of
        them. -> the entry node's acknowledgements."""
        entry = self.leader_for(self.head() + 1)
        results = entry.txpool.submit_batch(batch)
        entry.tx_sync.maintain()
        last_head, last_progress = self.head(), time.monotonic()
        while entry.txpool.pending_count() > 0:
            now, h = time.monotonic(), self.head()
            if h != last_head:
                last_head, last_progress = h, now
            elif now - last_progress > STALL_S:
                raise RuntimeError(f"chain stalled at height {h}")
            if not self.leader_for(h + 1).sealer.seal_and_submit():
                time.sleep(0.002)
        for nd in self.present:
            nd.scheduler.drain_commits(60.0)
        tip = max(nd.block_number() for nd in self.present)
        deadline = time.monotonic() + 30.0
        while any(nd.block_number() < tip for nd in self.present):
            if time.monotonic() > deadline:
                raise RuntimeError(f"replicas did not converge on height {tip}")
            time.sleep(0.002)
        return results

    def catch_up(self, until: float | None = None) -> None:
        """Reconnect the replica that was away and let its block sync run to
        the chain's head. Past ``until`` (perf_counter) its requests are
        dropped: what it has downloaded is applied, nothing more asked for."""
        from fisco_bcos_tpu.front.front import ModuleID

        away_id, target = self.away.node_id, max(nd.block_number() for nd in self.live)
        if until is not None:
            self.gw.dropped = lambda module, src, _dst: (
                module == ModuleID.BLOCK_SYNC and src == away_id
                and time.perf_counter() >= until
            )
        try:
            self.gw.connect(self.away.front)
            self.present = list(self.nodes)
            self.live[0].block_sync.broadcast_status()  # the replica hears, and asks
            while self.away.block_number() < target and (
                until is None or time.perf_counter() < until
            ):
                self.away.block_sync.maintain()
                time.sleep(0.002)
        finally:
            self.gw.dropped = None

    def stop(self) -> None:
        for nd in self.nodes:
            nd.stop()


class ServingPeer:
    """A peer of the benchmark's own on the gateway: says it stands one block
    past the replica and answers its block requests with what it was given,
    one answer a request. Before each answer it notes what the replica shows."""

    def __init__(self, chain: Chain, number: int, answers: list[bytes]):
        from fisco_bcos_tpu.crypto.suite import ecdsa_suite
        from fisco_bcos_tpu.front.front import FrontService, ModuleID
        from fisco_bcos_tpu.sync import block_sync

        self.chain, self.answers, self.seen = chain, list(answers), []
        self.sync, self.module = block_sync, ModuleID.BLOCK_SYNC
        self.node_id = ecdsa_suite().signature_impl.generate_keypair(secret=0x5E21E).pub
        self.front = FrontService(self.node_id)
        self.front.register_module(self.module, self._on_message)
        chain.gw.connect(self.front)
        genesis = chain.away.ledger.block_hash_by_number(0)
        self.status = block_sync._encode_status(
            block_sync.SyncStatus(number, b"\x5e" * 32, genesis))
        self.number = number

    def note(self) -> dict:
        away = self.chain.away
        return {
            "height": away.block_number(),
            "stored": away.ledger.header_by_number(self.number) is not None,
            "strikes": dict(away.block_sync._strikes).get(self.node_id, 0),
        }

    def _on_message(self, src: bytes, payload: bytes) -> None:
        if payload[0] != int(self.sync.SyncPacket.REQUEST):
            return
        self.seen.append(self.note())
        if self.answers:
            self.front.send_message(
                self.module, src, self.sync._encode_response([self.answers.pop(0)]))

    def serve(self) -> None:
        self.front.broadcast(self.module, self.status)
        self.seen.append(self.note())
        self.chain.gw.disconnect(self.node_id)


class Cell:
    # The window admits nothing: the backlog entered the pool in set-up, and a
    # downloaded block's transactions are re-verified by BlockSync, not
    # submitted. So the window writes no ``txpool.submit_batch``, ``txsync.push``
    # or ``txsync.maintain`` span, the ``admit_*`` quantities find nothing to read
    # (``stage_parts.py``), and the manifest keeps this driver's cells off their
    # lists: stated here once, read by ``tests/benchmark_checks/manifest_rules.py``.
    # A driver that does not say so admits in its window.
    window_admits = False

    def __init__(self, config: dict, traffic: dict, seed: int, spans):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.spans = spans
        self.batch_txs = int(traffic["batch_txs"])
        self.trace_blocks = int(traffic["trace_blocks"])
        # what set-up signs beyond the warm batches and the one served broken
        self.backlog_blocks = min(
            int(traffic["backlog_blocks"]), int(traffic["corpus_batches"]) - self.trace_blocks)
        self.series: list[dict] = []
        self.traced_series: list[dict] = []
        self.attempted = 0
        self.setup_parts: dict[str, float] = {}
        self.held: dict[int, list] = {}  # height -> [(lane, hash, sender)] as the replica executed
        self.served = None
        self.chain = self.trace_chain = None

    # -- set-up --------------------------------------------------------------

    def setup(self, seconds: float) -> None:
        from fisco_bcos_tpu.crypto import admission
        from fisco_bcos_tpu.sync import block_sync
        import numpy as np

        if not hasattr(block_sync, "VERIFY_LANES_MAX") or self.backlog_blocks < 1:
            print("benchmark: the block sync of the program in this checkout does not "
                  "re-verify downloaded blocks (sync/block_sync.VERIFY_LANES_MAX)",
                  file=sys.stderr)
            raise SystemExit(3)  # run.RC_NO_PROGRAM
        t = time.monotonic()
        self.chain, self.trace_chain = Chain(self.config), Chain(self.config)
        self.setup_parts["chain_s"] = time.monotonic() - t

        t = time.monotonic()
        self.corpus = Corpus(
            self.traffic, self.seed,
            block_limit=self.chain.head() + int(self.config["block_limit_ahead"]),
        )
        self.corpus.sign_until(1)
        first = self.corpus.batches[0]
        payloads = [tx.encode_data() for tx in first]
        sigs = np.stack([np.frombuffer(tx.signature, np.uint8) for tx in first])
        warm_error: list[BaseException] = []

        def warm_admission() -> None:
            # the plane worker traces and loads both admission shapes while
            # this thread signs the corpus: the sealing replicas' (a block's
            # bucket, the public entry as air4 warms it) and the gather's
            # (Node.warmup: whole blocks up to VERIFY_LANES_MAX lanes)
            try:
                admission.admit_batch(payloads, sigs)
                self.chain.away.warmup(batch_sizes=())
            except BaseException as e:  # re-raised on the main thread below
                warm_error.append(e)

        t_warm = time.monotonic()
        warm = threading.Thread(target=warm_admission, name="bench-warm-admission")
        warm.start()
        # warm batches, the backlog, the traced gather, the block served broken
        self.first_backlog = WARM_BATCHES
        self.first_traced = self.first_backlog + self.backlog_blocks
        self.broken_k = self.first_traced + self.trace_blocks
        self.corpus.sign_until(self.broken_k + 1)
        self.setup_parts["corpus_s"] = time.monotonic() - t
        warm.join()
        if warm_error:
            raise warm_error[0]
        self.setup_parts["admission_programs_s"] = time.monotonic() - t_warm

        t = time.monotonic()
        for k in range(WARM_BATCHES):  # all four level, every shape of a block resident
            self.chain.commit(self.corpus.batches[k])
        self.setup_parts["warm_batches_s"] = time.monotonic() - t

        t = time.monotonic()
        rng = random.Random(self.seed ^ 0xCA7C4)
        self.picks = sorted(
            (rng.randrange(self.backlog_blocks), rng.randrange(self.batch_txs))
            for _ in range(SAMPLE_TXS)
        )
        self.start_height = self.chain.away.block_number()
        self.chain.away.scheduler.on_committed.append(self._hold)
        self.chain.disconnect()
        for k in range(self.first_backlog, self.first_traced):
            self._acknowledged(self.chain.commit(self.corpus.batches[k]))
        self.trace_chain.disconnect()
        for k in range(self.first_traced, self.broken_k):
            self._acknowledged(self.trace_chain.commit(self.corpus.batches[k]))
        self.setup_parts["backlog_s"] = time.monotonic() - t

    @staticmethod
    def _acknowledged(results) -> None:
        bad = sum(1 for r in results if int(r.status) != 0)
        if bad:
            raise RuntimeError(f"set-up: {bad} valid transactions not acknowledged")

    def _hold(self, number: int, block) -> None:
        """On the replica's commit-notify worker: the hash and sender the
        replica executed the sampled lanes of this block with."""
        k = number - self.start_height - 1
        lanes = [i for kk, i in self.picks if kk == k]
        self.held[number] = [
            (i, bytes(block.transactions[i]._hash or b""), bytes(block.transactions[i].sender))
            for i in lanes if i < len(block.transactions)
        ]

    # -- the window ----------------------------------------------------------

    def _applied(self, lo: float, hi: float, height0: int) -> list[dict]:
        """One row a block the replica applied in [lo, hi), from the node's
        own ``sync.apply_block`` spans: when it was committed, and how long
        after the block before it (a gather's first block carries the
        gather's verification)."""
        from fisco_bcos_tpu.observability.tracer import TRACER

        rows, last = [], lo
        for r in sorted(TRACER.spans(), key=lambda r: r.ts):
            if r.name == "sync.apply_block" and lo <= r.ts < hi:
                end = r.ts + r.dur
                rows.append({
                    "k": len(rows), "height": height0 + len(rows) + 1,
                    "commit_s": end - lo, "block_ms": (end - last) * 1e3,
                    "apply_ms": r.dur * 1e3,
                })
                last = end
        return rows

    def window(self, seconds: float) -> None:
        away = self.chain.away
        self.head = max(nd.block_number() for nd in self.chain.live)
        self.committed0 = away.ledger.total_transaction_count()
        self.sync0 = sync_counters.snapshot()
        self.t0 = time.perf_counter()
        with self.spans.span("bench.catch_up"):
            self.chain.catch_up(until=self.t0 + seconds)
        self.t1 = time.perf_counter()
        self.sync1 = sync_counters.snapshot()
        self.committed1 = away.ledger.total_transaction_count()
        self.window_blocks = away.block_number() - self.start_height
        self.closed_at_head = away.block_number() == self.head
        self.attempted = self.window_blocks * self.batch_txs
        self.series = self._applied(self.t0, self.t1, self.start_height)
        # a block's share of the window as a row of the block path: from the
        # commit before it to its own, so that the readers of the chain
        # cells' split (benchmark/program_spans.py) read this cell's too
        edge = self.t0
        for row in self.series:
            self.spans.rows.append(("bench.seal_and_submit", edge, self.t0 + row["commit_s"]))
            edge = self.t0 + row["commit_s"]

    def traced(self, blocks: int) -> None:
        """One more gather under the profiler: the second chain's replica
        catches up with the ``trace_blocks`` its three peers committed in
        set-up."""
        height0 = self.trace_chain.away.block_number()
        lo = time.perf_counter()
        with self.spans.span("bench.catch_up"):
            self.trace_chain.catch_up()
        self.traced_series = self._applied(lo, time.perf_counter(), height0)

    def end_to_end(self) -> dict:
        return {"committed_tps": (self.committed1 - self.committed0) / (self.t1 - self.t0)}

    # -- correct -------------------------------------------------------------

    def after_window(self) -> None:
        """Finish the catch-up if the window closed on the clock; then one
        more block, served by a peer of the benchmark's own: first with four
        seeded lanes broken (r = 0, s = 0, r = n, s = n; header, QC and
        payloads genuine), which the replica must refuse, then as it is."""
        from fisco_bcos_tpu.protocol.block import Block

        chain = self.chain
        if not self.closed_at_head:
            chain.catch_up()
        chain.away.scheduler.drain_notifications(60.0)
        chain.disconnect()
        self._acknowledged(chain.commit(self.corpus.batches[self.broken_k]))
        number = self.head + 1
        genuine = chain.live[0].ledger.block_by_number(number, with_txs=True).encode()
        blk = Block.decode(genuine)
        lanes = random.Random(self.seed ^ 0xC0881).sample(range(len(blk.transactions)), 4)
        zero, order = bytes(32), SECP_N.to_bytes(32, "big")
        for lane, (r, s) in zip(lanes, ((zero, None), (None, zero), (order, None), (None, order))):
            tx = blk.transactions[lane]
            sig = bytes(tx.signature)
            tx.signature = (r or sig[:32]) + (s or sig[32:64]) + sig[64:]
            tx._wire = None
        self.broken_lanes = sorted(lanes)
        chain.gw.connect(chain.away.front)
        self.served = ServingPeer(chain, number, [blk.encode(), genuine])
        self.served.serve()
        self.served_bytes = {"broken": blk.encode(), "genuine": genuine}

    def observe(self) -> dict:
        """What the system shows after the window, in plain values: heights,
        the state root of every backlog height on the replica and on its
        peers, the balance the replica reads back for every user of the
        backlog, the sampled transactions as its ledger stores them with the
        hash and sender it executed them with, what block sync counted, and
        what the replica showed around the block served broken."""
        from fisco_bcos_tpu.codec.abi import ABICodec
        from fisco_bcos_tpu.crypto.suite import ecdsa_suite
        from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
        from fisco_bcos_tpu.protocol.transaction import TransactionFactory

        suite = ecdsa_suite()
        codec, fac = ABICodec(suite.hash), TransactionFactory(suite)
        away, live = self.chain.away, self.chain.live
        heights = range(self.start_height + 1, self.head + 1)
        backlog = [live[0].ledger.block_by_number(n, with_txs=True).encode() for n in heights]
        users = [rec[0] for k in range(self.first_backlog, self.first_traced)
                 for rec in self.corpus.records[k]]
        balances = {}
        for u in users:
            call = fac.create(
                chain_id="chain0", group_id="group0", block_limit=0, nonce="",
                to=DAG_TRANSFER_ADDRESS, input=codec.encode_call("userBalance(string)", u))
            code, bal = codec.decode_output(["uint256", "uint256"], away.scheduler.call(call).output)
            balances[u] = bal if code == 0 else None
        sample = []
        for k, i in self.picks:
            number = self.start_height + 1 + k
            held = {lane: (h, sender) for lane, h, sender in self.held.get(number, [])}
            hashes = away.ledger.tx_hashes_by_number(number)
            stored = away.ledger.tx_by_hash(hashes[i]) if i < len(hashes) else None
            sample.append(None if stored is None or i not in held else {
                "data": stored.encode_data(), "sig": bytes(stored.signature),
                "hash": held[i][0], "sender": held[i][1], "ledger_hash": hashes[i],
            })
        roots = lambda nd: [  # noqa: E731
            (h.state_root.hex() if (h := nd.ledger.header_by_number(n)) is not None else None)
            for n in heights
        ]
        return {
            "backlog": backlog,
            "committee": [nd.node_id for nd in self.chain.nodes],
            "height": self.start_height + self.window_blocks, "head": self.head,
            "closed_at_head": self.closed_at_head,
            "committed": self.committed1 - self.committed0,
            "roots": roots(away), "peer_roots": [roots(nd) for nd in live],
            "balances": balances, "sample": sample,
            "sync": {k: self.sync1[k] - self.sync0[k]
                     for k in ("lanes", "calls", "applied", "refused")},
            "served": [dict(s) for s in self.served.seen],
            "final_height": away.block_number(),
            "final_root": away.ledger.header_by_number(away.block_number()).state_root.hex(),
            "peer_final_root": live[0].ledger.header_by_number(self.head + 1).state_root.hex(),
        }

    def compare(self, seen: dict) -> list[dict]:
        """The plain reference against what was observed. Every comparison is
        exact: the limit of each number is 0."""
        want: dict[str, int] = {}
        for raw in seen["backlog"]:
            refsync.replay_user_add(refsync.split_block(raw)[1], refsync.Secp, want)
        balance_off = sum(1 for u, got in seen["balances"].items() if got != want.get(u))
        balance_off += sum(1 for u in want if u not in seen["balances"])
        roots_off = sum(
            1 for n, mine in enumerate(seen["roots"])
            if mine is None or any(peer[n] != mine for peer in seen["peer_roots"])
        )
        sample_off = 0
        for (k, i), got in zip(self.picks, seen["sample"]):
            who = self.corpus.records[self.first_backlog + k][i][2]
            if got is None or got["hash"] != got["ledger_hash"] or not _plain_admits(
                got["data"], got["sig"], self.corpus.secrets[who], got["hash"], got["sender"]
            ):
                sample_off += 1
        served = seen["served"]
        # the notes: before the first answer (the broken block), before the
        # second (the genuine one, asked for again), after both
        broken_applied = sum(1 for s in served[1:2] if s["height"] != served[0]["height"] or s["stored"])
        strikes_off = abs(served[1]["strikes"] - 1) if len(served) > 1 else 1
        genuine_off = int(
            len(served) != 3 or seen["final_height"] != seen["head"] + 1
            or seen["final_root"] != seen["peer_final_root"]
        )
        behind = seen["head"] - seen["height"] if seen["closed_at_head"] else 0
        uncommitted = (
            len(seen["backlog"]) * self.batch_txs - seen["committed"]
            if seen["closed_at_head"] else 0
        )
        return [
            {"name": "replica_blocks_behind_the_head", "value": behind, "limit": 0},
            {"name": "backlog_txs_not_committed", "value": uncommitted, "limit": 0},
            {"name": "heights_with_another_state_root", "value": roots_off, "limit": 0},
            {"name": "balances_differing_from_replay", "value": balance_off, "limit": 0},
            {"name": "sampled_txs_differing_from_plain_crypto", "value": sample_off, "limit": 0},
            {"name": "blocks_refused_in_window", "value": int(seen["sync"]["refused"]), "limit": 0},
            {"name": "broken_block_applied", "value": broken_applied, "limit": 0},
            {"name": "strikes_off_one_for_broken_block", "value": strikes_off, "limit": 0},
            {"name": "genuine_block_not_applied_after", "value": genuine_off, "limit": 0},
        ]

    def controls(self) -> dict:
        """Degraded variants of the observation, each breaking one guarantee
        the configuration states. ``correct`` has to come out false on each."""
        def broken_applied(seen):  # the block with r = 0 on a lane executed and stored
            seen["served"][1] = dict(
                seen["served"][1], height=seen["served"][0]["height"] + 1, stored=True, strikes=0)

        def lost_write(seen):  # a synced write missing on the replica
            user = sorted(seen["balances"])[self.seed % len(seen["balances"])]
            seen["balances"][user] = None

        def forked_root(seen):  # the replica on another state at one height
            seen["roots"][self.seed % len(seen["roots"])] = "00" * 32

        def neighbours_sender(seen):  # a sampled lane answered with its neighbour's sender
            n = self.seed % (len(seen["sample"]) - 1)
            other = next(s for s in seen["sample"][n + 1:] + seen["sample"][:n]
                         if s["sender"] != seen["sample"][n]["sender"])
            seen["sample"][n] = dict(seen["sample"][n], sender=other["sender"])

        return {
            "broken_applied": broken_applied, "lost_write": lost_write,
            "forked_root": forked_root, "neighbours_sender": neighbours_sender,
        }

    def failed_count(self) -> int:
        """Backlog transactions not committed on the replica when the window
        closed at the head, and transactions of blocks it refused there (the
        reference admits every block of the backlog)."""
        missing = (
            self.backlog_blocks * self.batch_txs - (self.committed1 - self.committed0)
            if self.closed_at_head else 0
        )
        return int(missing + (self.sync1["refused"] - self.sync0["refused"]) * self.batch_txs)

    def close(self) -> None:
        for chain in (self.chain, self.trace_chain):
            if chain is not None:
                chain.stop()

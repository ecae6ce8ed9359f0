"""Interleaving harnesses — small concurrent scenarios over the REAL hot
classes, driven by the :mod:`.interleave` explorer.

Each harness builds the subsystem's real objects (stub collaborators, no
background threads — the determinism contract), runs 2–3 workers through
a genuinely contended sequence, and asserts the invariant that a lost
update / torn sequence would break. The explorer preempts at every lock
edge and watched-field access, so the schedules these harnesses survive
include exactly the interleavings production would need OS-scheduler bad
luck to hit.

The real harnesses (``HARNESSES``) ride ``tool/check_races.py``'s
seeded sweep; :class:`RacyCounterHarness` is the *injected race* — the
canary proving the explorer actually finds and shrinks a data race (it
must FAIL; the suite asserts it does within a bounded seed budget).
"""

from __future__ import annotations

import hashlib
import threading


# -- injected fixture race ----------------------------------------------------


class _RacyCounter:
    """The textbook lost update: read and write with no lock (the lock
    exists and is deliberately unused — raceguard sees the empty lockset,
    the check sees the lost increment)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.value = 0

    def inc_racy(self) -> None:
        v = self.value  # preemption here is the lost-update window
        # analysis: allow(guarded-state, the injected race IS the fixture)
        self.value = v + 1

    def inc_guarded(self) -> None:
        with self._lock:
            v = self.value
            self.value = v + 1


class RacyCounterHarness:
    name = "racy-counter"

    def __init__(self, guarded: bool = False):
        self.guarded = guarded
        self.watch = [(_RacyCounter, ("value",))]

    def setup(self):
        return {"counter": _RacyCounter()}

    def threads(self, ctx):
        c = ctx["counter"]
        fn = c.inc_guarded if self.guarded else c.inc_racy

        def worker():
            fn()
            fn()

        return [("t1", worker), ("t2", worker)]

    def check(self, ctx):
        got = ctx["counter"].value
        assert got == 4, f"lost update: counter={got}, expected 4"


# -- DevicePlane coalescer ----------------------------------------------------


class DevicePlaneHarness:
    """Two submitters race the queue while a drainer runs the scheduler's
    pick/dispatch sequence — the stats counters, pending map and futures
    must stay coherent under any interleaving."""

    name = "device-plane"

    def __init__(self):
        from ..device.plane import DevicePlane

        self.watch = [(DevicePlane, (
            "requests", "dispatches", "merged_requests", "items", "_busy",
        ))]

    def setup(self):
        from ..device.plane import DevicePlane

        plane = DevicePlane(
            window_ms=0, high_water=64, starvation_ms=1e9, autostart=False
        )
        return {"plane": plane, "futs": []}

    def threads(self, ctx):
        import time

        plane = ctx["plane"]
        futs = ctx["futs"]

        def exec_fn(reqs):
            return [r.n for r in reqs]

        def submitter(n):
            def run():
                futs.append((n, plane.submit("verify", None, n, exec_fn)))

            return run

        def drainer():
            for _ in range(200):
                done = [f.done() for _, f in list(futs)]
                if len(done) == 2 and all(done):
                    return
                with plane._cv:
                    picked = plane._pick_ready_locked(time.perf_counter())
                if picked is not None:
                    op, reqs, deferred = picked
                    plane._note_deferred(op, deferred)
                    plane._dispatch(op, reqs)

        return [("sub1", submitter(1)), ("sub2", submitter(2)),
                ("drain", drainer)]

    def check(self, ctx):
        plane = ctx["plane"]
        futs = ctx["futs"]
        assert len(futs) == 2, f"submissions lost: {len(futs)}"
        for n, f in futs:
            assert f.done(), f"future for n={n} never resolved"
            assert f.result(timeout=0) == n, "result misrouted across slices"
        st = plane.stats()
        assert st["requests"] == 2 and st["items"] == 3, st
        assert st["queue_depth"] == 0, st
        assert 1 <= st["dispatches"] <= 2, st


# -- ProofPlane singleflight --------------------------------------------------


class _FakeReceipt:
    def __init__(self, number):
        self.block_number = number


class _FakeProofLedger:
    def __init__(self, tx_hashes):
        self.txs = list(tx_hashes)
        self.alive = True

    def receipt_by_hash(self, h):
        return _FakeReceipt(1) if self.alive and h in self.txs else None

    def block_hash_by_number(self, number):
        return b"B" * 32 if self.alive and number == 1 else None

    def tx_hashes_by_number(self, number):
        return list(self.txs) if self.alive and number == 1 else []


class _FakeTree:
    def __init__(self, leaves):
        self.levels = [list(leaves), [hashlib.sha256(b"".join(leaves)).digest()]]
        self.n = len(leaves)
        self.width = max(len(leaves), 2)


class _FakeProofSuite:
    def merkle_tree(self, arr):
        return _FakeTree([bytes(row) for row in arr])


class ProofPlaneHarness:
    """Concurrent cache misses for one height must coalesce on the
    singleflight future while an invalidator races evictions — every
    caller still gets a proof for the live identity, exactly one build
    per generation, and the hit/miss ledger stays consistent."""

    name = "proof-singleflight"

    def __init__(self):
        from ..proofs.plane import ProofPlane

        self.watch = [(ProofPlane, (
            "requests", "hits", "misses", "builds_lazy", "coalesced_builds",
        ))]

    def setup(self):
        from ..proofs.plane import ProofPlane

        h1, h2 = b"\x01" * 32, b"\x02" * 32
        ledger = _FakeProofLedger([h1, h2])
        plane = ProofPlane(ledger, _FakeProofSuite(), capacity=4)
        return {"plane": plane, "hashes": (h1, h2), "out": {}}

    def threads(self, ctx):
        plane = ctx["plane"]
        h1, h2 = ctx["hashes"]
        out = ctx["out"]

        def reader(name, h):
            def run():
                out[name] = plane.proof_batch([h])

            return run

        def invalidator():
            plane.invalidate(1, reason="rollback")

        return [("r1", reader("r1", h1)), ("r2", reader("r2", h2)),
                ("inval", invalidator)]

    def check(self, ctx):
        plane = ctx["plane"]
        out = ctx["out"]
        assert set(out) == {"r1", "r2"}, f"readers lost: {sorted(out)}"
        for name, expect_idx in (("r1", 0), ("r2", 1)):
            res = out[name][0]
            assert res is not None, f"{name}: proof missing for a live height"
            number, items, idx, n = res
            assert number == 1 and idx == expect_idx and n == 2, res
        st = plane.stats()
        assert st["hits"] + st["misses"] == st["requests"], st
        assert st["builds_lazy"] >= 1, st
        # generations: at most one build per eviction epoch (initial +
        # post-invalidate), never one per caller
        assert st["builds_lazy"] <= 2, st


# -- AdmissionQuotas strikes --------------------------------------------------


class AdmissionQuotasHarness:
    """Two sources of strikes race the demotion edge while a reader takes
    snapshots — strikes must not be lost (two strikes at limit 2 ⇒
    demoted), grants must match the bucket, and the shed ledger adds up."""

    name = "admission-quotas"

    def __init__(self):
        from ..txpool.quota import AdmissionQuotas

        self.watch = [(AdmissionQuotas, ("_groups",))]

    def setup(self):
        from ..txpool.quota import AdmissionQuotas

        quotas = AdmissionQuotas(
            default_rate=1000.0, default_burst=1000.0, strike_limit=2,
            strike_window_s=600.0, demote_s=600.0,
        )
        return {"q": quotas, "granted": []}

    def threads(self, ctx):
        q = ctx["q"]
        granted = ctx["granted"]

        def striker():
            granted.append(q.try_admit("g", 5))
            q.note_invalid("g", "spammer", 3)

        def reader():
            q.demoted("g", "spammer")
            q.snapshot()
            q.count_demoted_drop("g", 2)

        return [("s1", striker), ("s2", striker), ("read", reader)]

    def check(self, ctx):
        q = ctx["q"]
        assert sum(ctx["granted"]) == 10, ctx["granted"]
        assert q.demoted("g", "spammer"), "strike lost: source not demoted"
        snap = q.snapshot()["g"]
        assert snap["demote_drops"] == 2, snap
        assert snap["demoted_sources"] == ["spammer"], snap


# -- Scheduler commit markers -------------------------------------------------


class _FakeSchedHeader:
    def __init__(self, number):
        self.number = number
        self.state_root = b"\x00" * 32
        self.txs_root = b"\x00" * 32
        self.receipts_root = b"\x00" * 32

    def hash(self, _suite):
        return b"H%031d" % self.number

    def clear_hash_cache(self):
        pass


class _FakeSchedBlock:
    def __init__(self, header):
        self.header = header
        self.transactions = []
        self.tx_metadata = []
        self.receipts = []

    def tx_hashes(self, _suite):
        return []


class _FakeSchedLedger:
    def __init__(self):
        self.height = 0

    def block_number(self):
        return self.height

    def prewrite_block(self, block, writes):
        pass


class _FakeSchedExecutor:
    def __init__(self, ledger):
        self._ledger = ledger

    def prepare(self, params, extra_writes=None):
        pass

    def commit(self, params):
        self._ledger.height = params.number


class _InlineNotify:
    """Stands in for the commit-notify Worker: listeners run synchronously
    on the committing worker (no unmanaged thread may race a schedule)."""

    def start(self):
        pass

    def stop(self):
        pass

    def post(self, fn):
        fn()


class SchedulerHarness:
    """Two committers and a storage-term switcher race the in-flight
    commit marker and its condition variable — commits must land in
    height order, the marker must never leak, and switch_term must wait
    out (never deadlock against) an in-flight 2PC."""

    name = "scheduler-commit"

    def __init__(self):
        from ..scheduler.scheduler import Scheduler

        self.watch = [(Scheduler, ("term", "_committing_thread"))]

    def setup(self):
        from ..scheduler.scheduler import ExecutedBlock, Scheduler

        ledger = _FakeSchedLedger()
        executor = _FakeSchedExecutor(ledger)
        sched = Scheduler(
            executor, ledger, backend=None, suite=None,
            notify_worker=_InlineNotify(),
        )
        for n in (1, 2):
            header = _FakeSchedHeader(n)
            sched._executed[n] = ExecutedBlock(
                header, _FakeSchedBlock(header), tx_hashes=()
            )
        committed: list[int] = []
        sched.on_committed.append(lambda n, _b: committed.append(n))
        return {"sched": sched, "ledger": ledger, "committed": committed}

    def threads(self, ctx):
        from ..scheduler.scheduler import SchedulerError

        sched = ctx["sched"]

        def committer(number):
            header = _FakeSchedHeader(number)

            def run():
                for _ in range(50):
                    try:
                        sched.commit_block(header)
                        return
                    except SchedulerError:
                        # out of order (predecessor not booked) or dropped
                        # by a term switch: retry / give up respectively
                        if number not in sched._executed:
                            return
                return

            return run

        def switcher():
            sched.switch_term()

        return [("c1", committer(1)), ("c2", committer(2)),
                ("switch", switcher)]

    def check(self, ctx):
        sched = ctx["sched"]
        committed = ctx["committed"]
        assert sched.term == 1, f"term switch lost: {sched.term}"
        assert not sched._committing, f"marker leaked: {sched._committing}"
        assert sched._committing_thread is None, "committer identity leaked"
        # commits that happened landed in height order, and the ledger head
        # equals the highest booked height (nothing torn by the switch)
        assert committed == sorted(committed), committed
        assert ctx["ledger"].height == (committed[-1] if committed else 0)


# -- Pipelined commit: rollback edges ----------------------------------------


class _FlakyCommitExecutor(_FakeSchedExecutor):
    """Commit of a CHOSEN height fails exactly once (the async-commit
    rollback edge), then succeeds on the re-drive."""

    supports_preexec = True

    def __init__(self, ledger, fail_number: int):
        super().__init__(ledger)
        self.fail_number = fail_number
        self.failed_once = False

    def commit(self, params):
        if params.number == self.fail_number and not self.failed_once:
            self.failed_once = True
            raise ConnectionError("injected commit fault")
        super().commit(params)

    # speculative-execution stubs (the harness block carries no txs)
    def next_block_header(self, header, base=None):
        pass

    def get_hash_async(self):
        return lambda: b"\x00" * 32

    def block_state(self, number):
        return object()  # a chained overlay stand-in


class _FakePipelineBlock(_FakeSchedBlock):
    def calculate_txs_root_async(self, _suite):
        return lambda: b"\x00" * 32

    def calculate_receipts_root_async(self, _suite):
        return lambda: b"\x00" * 32


class PipelinedCommitHarness:
    """The flood-pipeline rollback edges (ISSUE 14): a committer whose 2PC
    fails once and re-drives, a committer for the NEXT height queued
    behind it, a speculative lazy-roots execution chained above both, and
    a storage-term switcher — the in-flight marker, the pending-root
    resolvers and the commit order must stay coherent under every
    interleaving (commit-failure of N with speculative N+1 executed, and
    a storage switch mid-pipeline)."""

    name = "pipelined-commit"

    def __init__(self):
        from ..scheduler.scheduler import Scheduler

        self.watch = [
            (Scheduler, ("term", "_committing_thread", "_commits_queued")),
        ]

    def setup(self):
        from ..scheduler.scheduler import ExecutedBlock, Scheduler

        ledger = _FakeSchedLedger()
        executor = _FlakyCommitExecutor(ledger, fail_number=1)
        sched = Scheduler(
            executor, ledger, backend=None, suite=None,
            notify_worker=_InlineNotify(), commit_worker=_InlineNotify(),
        )
        for n in (1, 2):
            header = _FakeSchedHeader(n)
            sched._executed[n] = ExecutedBlock(
                header, _FakePipelineBlock(header), tx_hashes=(),
                post_state=object(),
            )
        committed: list[int] = []
        outcomes: list[tuple[int, bool]] = []
        sched.on_committed.append(lambda n, _b: committed.append(n))
        return {
            "sched": sched, "ledger": ledger, "committed": committed,
            "outcomes": outcomes,
        }

    def threads(self, ctx):
        from ..scheduler.scheduler import SchedulerError

        sched = ctx["sched"]
        outcomes = ctx["outcomes"]

        def committer(number):
            header = _FakeSchedHeader(number)

            def run():
                for _ in range(50):
                    try:
                        sched.commit_block_async(
                            header,
                            on_done=lambda n, e: outcomes.append((n, e is None)),
                        )
                    except SchedulerError:
                        if number not in sched._executed:
                            return  # dropped by the term switch
                        continue
                    # inline worker: the 2PC already ran; re-drive until
                    # this height is durably booked or the switch drops it
                    if ctx["ledger"].height >= number:
                        return
                return

            return run

        def speculator():
            # lazy-roots speculative execution of N+2 chained on N+1's
            # post-state, racing the commits and the term switch
            header = _FakeSchedHeader(3)
            block = _FakePipelineBlock(header)
            try:
                sched.execute_block(block, lazy_roots=True)
            except SchedulerError:
                pass  # chain not ready / dropped mid-race: a legal outcome

        def switcher():
            sched.switch_term()

        return [
            ("c1", committer(1)), ("c2", committer(2)),
            ("spec", speculator), ("switch", switcher),
        ]

    def check(self, ctx):
        sched = ctx["sched"]
        committed = ctx["committed"]
        assert sched.term == 1, f"term switch lost: {sched.term}"
        assert not sched._committing, f"marker leaked: {sched._committing}"
        assert sched._commits_queued == 0, sched._commits_queued
        assert sched._committing_thread is None, "committer identity leaked"
        assert committed == sorted(committed), committed
        assert ctx["ledger"].height == (committed[-1] if committed else 0)
        # a lazily-executed speculation either resolved its roots, was
        # dropped by the switch, or still holds its resolvers — never a
        # half-resolved header
        eb = sched._executed.get(3)
        if eb is not None and eb.pending_roots is None:
            assert eb.header.state_root == b"\x00" * 32


# -- Pipeline observatory stage machine ---------------------------------------


class PipelineObsHarness:
    """Two pipeline workers drive busy/blocked transitions on ONE stage
    while a sampler thread takes snapshots and watermark sweeps — the
    interval counters must not lose updates, the thread counts must
    return to zero, and no snapshot may tear (ISSUE 9: the recorder is
    always-on shared state touched by every pipeline worker plus the
    background sampler)."""

    name = "pipeline-obs"

    def __init__(self):
        from ..observability.pipeline import PipelineRecorder, StageStats

        self.watch = [
            (PipelineRecorder, ("_stages", "_marks")),
            (StageStats, (
                "busy_ms", "intervals", "blocked_intervals", "n_busy",
                "n_blocked", "state",
            )),
        ]

    def setup(self):
        from ..observability.pipeline import PipelineRecorder

        # deterministic injected clock (the explorer forbids wall clocks);
        # monotone under any interleaving because += happens under the
        # recorder's (instrumented) lock or a worker-local read
        ticks = {"t": 0.0}
        lock = threading.Lock()

        def clock():
            with lock:
                ticks["t"] += 1.0
                return ticks["t"]

        rec = PipelineRecorder(clock=clock, enabled=True, emit_metrics=False)
        rec.add_probe("depth", lambda: 1)
        return {"rec": rec, "snaps": []}

    def threads(self, ctx):
        rec = ctx["rec"]
        snaps = ctx["snaps"]

        def worker():
            for _ in range(2):
                with rec.busy("stage"):
                    with rec.blocked("downstream"):
                        pass

        def sampler():
            rec.sample_once()
            snaps.append(rec.snapshot())

        return [("w1", worker), ("w2", worker), ("sample", sampler)]

    def check(self, ctx):
        rec = ctx["rec"]
        snap = rec.snapshot()["stage"]
        # the lost-update canaries: 2 workers x 2 intervals each
        assert snap["intervals"] == 4, snap
        assert snap["blocked_intervals"] == 4, snap
        assert snap["active_threads"] == 0, snap
        assert snap["blocked_threads"] == 0, snap
        assert snap["state"] == "idle", snap
        assert snap["busy_ms"] > 0 and snap["blocked_ms"]["downstream"] > 0, snap
        marks = rec.watermarks()
        assert marks["depth"]["n"] == 1, marks
        for s in ctx["snaps"]:
            st = s.get("stage")
            if st is not None:
                assert st["active_threads"] >= 0 and st["intervals"] <= 4, st


# -- QuorumCollector vote accumulator -----------------------------------------


class _StubQCScheme:
    """Deterministic, crypto-free QC scheme: the explorer needs pure
    control flow (a pairing check inside a schedule would swamp the
    preemption budget and add nothing — the contention is in the
    accumulator, not the algebra)."""

    name = "ed25519"  # a registered wire id so certs encode/decode
    pub_len = 4

    @staticmethod
    def _expect(pub: bytes, msg32: bytes) -> bytes:
        return b"sig:" + pub + msg32[:4]

    def verify_one(self, qc_pub, msg32, sig):
        return sig == self._expect(qc_pub, msg32)

    def build_cert(self, sig_by_idx, committee):
        from ..consensus.qc import QuorumCert

        idxs = sorted(sig_by_idx)
        return QuorumCert(
            scheme=self.name,
            committee=committee,
            bitmap=QuorumCert.make_bitmap(idxs, committee),
            agg_sig=b"".join(sig_by_idx[i] for i in idxs),
        )

    def verify_cert(self, cert, qc_pubs, msg32):
        want = b"".join(self._expect(qc_pubs[i], msg32) for i in cert.signers())
        return bool(cert.signers()) and cert.agg_sig == want


class QuorumCollectorHarness:
    """Concurrent vote arrival races quorum admission (aggregate verify +
    seal-once memo) and view-change/commit resets on the ISSUE 12 vote
    accumulator — votes must never be lost (the counter sees every add),
    whichever admit runs last must seal a quorum certificate, and the
    seal memo/pending map must stay coherent under any interleaving."""

    name = "qc-collector"

    def __init__(self):
        from ..consensus.qc import QuorumCollector

        self.watch = [(QuorumCollector, (
            "votes", "aggregates", "fallbacks", "bad_votes", "sealed",
            "_pending",
        ))]

    KEY = (1, 5, 0, b"\xaa" * 32)  # (phase, number, view, hash)
    MSG = b"\xbb" * 32

    def setup(self):
        from ..consensus.qc import QuorumCollector
        from ..txpool.quota import get_quotas

        get_quotas().reset()  # strikes from prior seeds must not leak in
        scheme = _StubQCScheme()
        col = QuorumCollector(suite=None, scheme=scheme)
        pubs = [b"pk_%d" % i for i in range(4)]
        return {"col": col, "pubs": pubs, "scheme": scheme, "out": {}}

    def threads(self, ctx):
        col = ctx["col"]
        pubs = ctx["pubs"]
        scheme = ctx["scheme"]
        out = ctx["out"]

        def voter(idxs, name):
            def run():
                for i in idxs:
                    col.add_vote(
                        self.KEY, i, scheme._expect(pubs[i], self.MSG)
                    )
                out[name] = col.admit(
                    self.KEY, self.MSG, None, pubs, lambda i: 1, 3
                )

            return run

        def resetter():
            # non-destructive passes over the shared maps: pure lock/state
            # contention (number 5 survives reset_below(4); view 0 keys
            # survive reset_view(0))
            col.reset_view(0)
            col.reset_below(4)

        return [
            ("v1", voter([0, 1], "v1")),
            ("v2", voter([2, 3], "v2")),
            ("reset", resetter),
        ]

    def check(self, ctx):
        col = ctx["col"]
        out = ctx["out"]
        st = col.stats()
        assert st["votes"] == 4, f"lost votes: {st}"
        assert set(out) == {"v1", "v2"}, f"admits lost: {sorted(out)}"
        # whichever admit serialized last saw all four votes: it must have
        # sealed (or reused the first seal's memo)
        certs = [r[2] for r in out.values() if r[2] is not None]
        assert certs, f"no quorum sealed: {out}"
        for cert in certs:
            assert len(cert.signers()) >= 3, cert.signers()
        assert st["sealed"] >= 1 and st["bad_votes"] == 0, st
        assert st["fallbacks"] == 0, st


# -- Fleet observatory: round ledger + flight ring -----------------------------


class FleetObsHarness:
    """Two engine-side writers drive the SAME round's edges and votes
    (plus younger rounds and a view change) while the federation
    aggregator snapshots the ledger and a crash-flush drains the flight
    ring to disk (ISSUE 16): first-wins edges must survive re-delivery
    races, quorum votes must never be lost, a snapshot must not tear
    mid-round, and the flushed black box must parse back whole."""

    name = "fleet-obs"

    def __init__(self):
        from ..observability.flight import FlightRecorder
        from ..observability.roundlog import RoundLedger

        self.watch = [
            (RoundLedger, ("_rounds", "_view_changes")),
            # "?": the ring rides lock-free GIL-atomic appends by design —
            # only a reassignment of the ring itself may flag
            (FlightRecorder, ("?_ring",)),
        ]

    def setup(self):
        import tempfile

        from ..observability.flight import FlightRecorder
        from ..observability.roundlog import RoundLedger

        # deterministic injected clock (the explorer forbids wall clocks)
        ticks = {"t": 0.0}
        lock = threading.Lock()

        def clock():
            with lock:
                ticks["t"] += 1.0
                return ticks["t"]

        led = RoundLedger(node_tag="h0", cap=8, clock=clock, emit_metrics=False)
        fr = FlightRecorder(cap=64, clock=clock, wallclock=clock, enabled=True)
        return {
            "led": led, "fr": fr, "snaps": [],
            "dir": tempfile.mkdtemp(prefix="fleet-obs-"),
        }

    def threads(self, ctx):
        led = ctx["led"]
        fr = ctx["fr"]
        snaps = ctx["snaps"]

        def engine():
            # the engine worker: round 5's own edges + its quorum votes
            led.note(5, 0, "pre_prepare")
            for i in range(3):
                led.vote(5, 0, "prepare", i)
            led.note(5, 0, "prepared")
            fr.record("engine", "prepared", scope="h0", height=5)

        def transport():
            # transport threads race the same round (re-delivery included)
            led.vote(5, 0, "prepare", 3)
            led.note(5, 0, "pre_prepare")  # re-delivered frame: first wins
            for h in (6, 7, 8):
                led.note(h, 0, "pre_prepare")
            led.view_change(6, 0, 1, "timeout")
            fr.record("engine", "pre_prepare", scope="h0", height=6)

        def aggregator():
            snaps.append(led.snapshot())
            snaps.append(led.snapshot(height=5))

        def flusher():
            # the crash-flush door: ring + embedded ledger to disk
            fr.record("halt", "stop", scope="h0")
            ctx["path"] = fr.flush(
                "h0", "crash:test", directory=ctx["dir"],
                rounds=led.snapshot(),
            )

        return [
            ("engine", engine), ("transport", transport),
            ("agg", aggregator), ("flush", flusher),
        ]

    def check(self, ctx):
        import json
        import shutil

        from ..observability.flight import post_mortem

        led = ctx["led"]
        final = led.snapshot()
        by_key = {(r["height"], r["view"]): r for r in final["rounds"]}
        # the lost-update canaries: every edge, every vote, the view change
        r5 = by_key[(5, 0)]
        assert {"pre_prepare", "prepared"} <= set(r5["events"]), r5
        assert set(r5["votes"]["prepare"]) == {"0", "1", "2", "3"}, r5
        for h in (6, 7, 8):
            assert (h, 0) in by_key, sorted(by_key)
        assert [vc["cause"] for vc in final["view_changes"]] == ["timeout"]
        # no torn snapshot: every observed round is structurally whole
        for snap in ctx["snaps"]:
            for r in snap["rounds"]:
                assert isinstance(r["events"], dict), r
                assert all(
                    isinstance(t, float) for vs in r["votes"].values()
                    for t in vs.values()
                ), r
        for r in ctx["snaps"][1::2]:  # the height-filtered snapshots
            assert all(x["height"] == 5 for x in r["rounds"]), r
        # the black box parses back whole, wherever the flush interleaved
        assert ctx.get("path"), "flight flush failed"
        with open(ctx["path"]) as f:
            doc = json.load(f)
        assert doc["reason"] == "crash:test", doc["reason"]
        names = {(e["category"], e["name"]) for e in doc["events"]}
        assert ("halt", "stop") in names, sorted(names)
        assert doc["rounds"]["node"] == "h0", doc["rounds"]
        pm = post_mortem(ctx["dir"])
        assert "h0" in pm["nodes"] and pm["timeline"], pm["nodes"]
        shutil.rmtree(ctx["dir"], ignore_errors=True)


# -- PBFT engine: off-lock QC admission (torn quorum) --------------------------


class _TornStubSig:
    """Deterministic outer-signature impl (the packet signature): pure
    string check, no crypto — the contention under test is the engine's
    verify queue, not the algebra."""

    @staticmethod
    def sign(kp, msg):
        return b"wire:" + kp.pub[:8] + msg[:8]

    @staticmethod
    def verify(pub, msg, sig):
        return sig == b"wire:" + pub[:8] + msg[:8]


class _TornStubSuite:
    name = "stub"
    signature_impl = _TornStubSig()

    @staticmethod
    def hash(data: bytes) -> bytes:
        return hashlib.sha256(data).digest()


class _TornKP:
    def __init__(self, pub: bytes, secret: int = 0):
        self.pub = pub
        self.secret = secret


class _TornQCScheme(_StubQCScheme):
    """The collector stub plus ``sign_vote`` (the engine signs its own
    votes through the scheme) and the registered ed25519 pub length so
    ``qc_ready()`` sees a fully-registered committee."""

    pub_len = 32

    def sign_vote(self, kp, msg32: bytes) -> bytes:
        return self._expect(kp.pub, msg32)


class TornQuorumHarness:
    """Concurrent PREPARE deliveries race the engine's OFF-LOCK aggregate
    QC admission (snapshot under the lock -> verify without it -> re-check
    the gate before completing) while a duplicate pre-prepare contends on
    the engine lock. A torn quorum — two completions, a completion against
    a stale snapshot, or a lost/duplicated verify job — is the bug class
    the double-gate re-check must exclude under EVERY interleaving."""

    name = "torn-quorum"

    def __init__(self):
        from ..consensus.engine import PBFTEngine, ProposalCache

        self.watch = [
            (PBFTEngine, ("_verify_jobs", "_verify_keys", "view")),
            (ProposalCache, ("prepared", "prepare_qc", "committed")),
        ]

    def setup(self):
        from ..consensus.audit import EVIDENCE
        from ..consensus.config import PBFTConfig
        from ..consensus.engine import PBFTEngine
        from ..consensus.messages import PacketType, PBFTMessage
        from ..consensus.qc import QuorumCollector, vote_preimage
        from ..front.front import FrontService
        from ..ledger.ledger import ConsensusNode
        from ..protocol.block import Block
        from ..protocol.block_header import BlockHeader
        from ..scheduler.scheduler import SchedulerError
        from ..txpool.quota import get_quotas

        get_quotas().reset()  # strikes from prior seeds must not leak in
        EVIDENCE.reset()
        suite = _TornStubSuite()
        scheme = _TornQCScheme()
        kps = [_TornKP(b"np_%d_" % i * 8, secret=i) for i in range(4)]
        qc_pubs = [bytes([0xA0 + i]) * 32 for i in range(4)]
        committee = [
            ConsensusNode(kp.pub, weight=1, qc_pub=qc_pubs[i])
            for i, kp in enumerate(kps)
        ]
        config = PBFTConfig(suite=suite, keypair=kps[0], nodes=committee)
        # pre-seed the QC keypair memo: the real derivation hashes the
        # consensus secret through the registered scheme — stubbed here
        config._qc_kp_cache = ("ed25519", _TornKP(qc_pubs[0]))

        class _Ledger:
            @staticmethod
            def block_number():
                return 0

            @staticmethod
            def block_hash_by_number(_n):
                return b"\x11" * 32

        class _Scheduler:
            @staticmethod
            def execute_block(_block, lazy_roots=False):
                from ..utils.error import ErrorCode

                raise SchedulerError(
                    ErrorCode.SCHEDULER_INVALID_BLOCK,
                    "stub: no execution in the harness",
                )

        class _TxPool:
            @staticmethod
            def mark_sealed(_hashes):
                pass

        eng = PBFTEngine(
            config, _Scheduler(), _TxPool(), _Ledger(), FrontService(kps[0].pub)
        )
        eng.qc = QuorumCollector(suite=None, scheme=scheme)
        eng.qc.strike_tagger = eng._qc_strike_tag

        completions = []
        real_complete = eng._complete_prepared

        def counting_complete(number, cache, agreeing, cert):
            completions.append(number)
            real_complete(number, cache, agreeing, cert)

        eng._complete_prepared = counting_complete

        # leader of (number=1, view=0) is index 1; this engine is index 0
        block = Block(header=BlockHeader(number=1))
        h = block.header.hash(suite)
        pp = PBFTMessage(
            packet_type=PacketType.PRE_PREPARE,
            view=0,
            number=1,
            proposal_hash=h,
            proposal_data=block.encode(),
        )
        pp.generated_from = 1
        pp.sign(suite, kps[1])

        def prepare_from(i):
            m = PBFTMessage(
                packet_type=PacketType.PREPARE, view=0, number=1,
                proposal_hash=h,
            )
            m.generated_from = i
            m.sign(suite, kps[i])
            m.qc_sig = scheme._expect(
                qc_pubs[i], vote_preimage(suite, PacketType.PREPARE, 0, 1, h)
            )
            return m

        # accept the proposal (our own PREPARE joins the cache) and bank
        # the leader's vote: 2 of quorum-3 in hand, the crossing vote
        # arrives on the contending threads
        eng.handle_message(pp)
        eng.handle_message(prepare_from(1))
        return {
            "eng": eng, "pp": pp, "completions": completions,
            "prepares": [prepare_from(2), prepare_from(3)],
        }

    def threads(self, ctx):
        eng = ctx["eng"]
        p2, p3 = ctx["prepares"]

        def deliver(m):
            def run():
                eng.handle_message(m)

            return run

        return [
            ("v2", deliver(p2)),
            ("v3", deliver(p3)),
            ("pp-dup", deliver(ctx["pp"])),
        ]

    def check(self, ctx):
        from ..consensus.audit import EVIDENCE

        eng = ctx["eng"]
        cache = eng._caches.get(1)
        assert cache is not None, "proposal cache vanished"
        assert ctx["completions"] == [1], (
            f"torn quorum: completions={ctx['completions']}"
        )
        assert cache.prepared, "quorum never admitted"
        assert cache.prepare_qc is not None, "no certificate sealed"
        assert len(cache.prepare_qc.signers()) >= 3, cache.prepare_qc.signers()
        assert 0 in cache.commits, "own COMMIT vote lost"
        assert not cache.committed, "committed on 1 commit vote"
        assert not eng._verify_jobs and not eng._verify_keys, (
            f"verify queue leaked: {list(eng._verify_jobs)}"
        )
        assert EVIDENCE.count() == 0, EVIDENCE.counts()


HARNESSES = {
    h.name: h
    for h in (DevicePlaneHarness, ProofPlaneHarness, AdmissionQuotasHarness,
              SchedulerHarness, PipelinedCommitHarness, PipelineObsHarness,
              QuorumCollectorHarness, FleetObsHarness, TornQuorumHarness)
}

FIXTURE_HARNESSES = {RacyCounterHarness.name: RacyCounterHarness}

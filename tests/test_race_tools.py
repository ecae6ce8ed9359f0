"""Runtime race tooling: the raceguard lockset recorder and the seeded
deterministic interleaving explorer (ISSUE 8's dynamic half).

Enforcement contracts pinned here:

1. the explorer is **bit-deterministic**: same seed ⇒ identical grant
   trace and schedule digest;
2. the **injected fixture race** (harnesses.RacyCounterHarness) is found
   within a bounded seed budget and shrinks to a *stable* minimal digest;
3. the guarded control and the four REAL harnesses (DevicePlane coalescer,
   ProofPlane singleflight, AdmissionQuotas, scheduler commit markers)
   survive seeded sweeps — the same harnesses tool/check_races.py sweeps
   at ≥256 seeds;
4. the raceguard state machine: single-thread churn stays silent,
   consistently-locked cross-thread traffic stays silent, disjoint
   locksets report exactly once per Class.field;
5. a schedule that deadlocks is reported as a deadlock outcome, not a
   hang.

Explorations run a few dozen short schedules each — wall-clock is
milliseconds per schedule, well inside the tier-1 budget.
"""

from __future__ import annotations

import threading

import pytest

from fisco_bcos_tpu.analysis.harnesses import (
    HARNESSES,
    AdmissionQuotasHarness,
    DevicePlaneHarness,
    PipelineObsHarness,
    PipelinedCommitHarness,
    ProofPlaneHarness,
    QuorumCollectorHarness,
    RacyCounterHarness,
    SchedulerHarness,
)
from fisco_bcos_tpu.analysis.interleave import (
    Explorer,
    find_and_shrink,
    replay,
    shrink,
    sweep,
)
from fisco_bcos_tpu.analysis.raceguard import RaceGuard

# -- raceguard unit coverage --------------------------------------------------


class _Watched:
    def __init__(self):
        self.x = 0


def _guard_with_manual_lockset():
    held = threading.local()
    guard = RaceGuard(lockset_fn=lambda: tuple(getattr(held, "l", ())))
    return guard, held


def _run(fn) -> None:
    t = threading.Thread(target=fn)
    t.start()
    t.join()


def test_raceguard_single_thread_never_reports():
    guard, held = _guard_with_manual_lockset()
    guard.watch(_Watched, ("x",))
    try:
        obj = _Watched()
        for _ in range(10):
            obj.x += 1  # exclusive: one thread, no lock, no report
    finally:
        guard.unwatch_all()
    assert guard.report() == []


def test_raceguard_consistent_lock_silent_disjoint_reports():
    guard, held = _guard_with_manual_lockset()
    guard.watch(_Watched, ("x",))
    try:
        good, bad = _Watched(), _Watched()

        def locked_bump(obj, lock):
            held.l = (lock,)
            obj.x += 1
            held.l = ()

        _run(lambda: locked_bump(good, "L"))
        _run(lambda: locked_bump(good, "L"))
        assert guard.report() == []
        _run(lambda: locked_bump(bad, "L1"))
        _run(lambda: locked_bump(bad, "L2"))  # disjoint: lockset empties
    finally:
        guard.unwatch_all()
    races = guard.report()
    assert len(races) == 1 and "_Watched.x" in races[0], races
    # reported once per Class.field even if hammered again
    guard.watch(_Watched, ("x",))
    try:
        _run(lambda: setattr(bad, "x", 9))
    finally:
        guard.unwatch_all()
    assert len(guard.report()) == 1


def test_raceguard_unwatch_restores_class():
    guard, _held = _guard_with_manual_lockset()
    orig_set = _Watched.__setattr__
    guard.watch(_Watched, ("x",))
    assert _Watched.__setattr__ is not orig_set
    guard.unwatch_all()
    assert _Watched.__setattr__ is orig_set


# -- explorer determinism + injected race -------------------------------------


def test_same_seed_identical_schedule_digest():
    a = Explorer(seed=1234).run(RacyCounterHarness())
    b = Explorer(seed=1234).run(RacyCounterHarness())
    assert a.digest == b.digest
    assert a.trace == b.trace
    assert a.decisions == b.decisions
    c = Explorer(seed=1235).run(RacyCounterHarness())
    assert c.digest != a.digest  # different seed explores a different order


def test_injected_race_found_and_shrunk_to_stable_digest():
    failing, small = find_and_shrink(
        lambda: RacyCounterHarness(), max_seeds=64
    )
    assert failing is not None, "injected race not found within 64 seeds"
    assert failing.failed and (failing.races or failing.status == "check")
    assert small is not None and small.failed
    # the shrink is idempotent and its digest is the race's stable identity
    again = shrink(lambda: RacyCounterHarness(), failing)
    assert again.digest == small.digest
    # replaying the minimal decisions reproduces the failure bit-for-bit
    re = replay(lambda: RacyCounterHarness(), small.decisions, seed=small.seed)
    assert re.failed and re.digest == small.digest


def test_guarded_counter_control_passes():
    outs, failing = sweep(lambda: RacyCounterHarness(guarded=True), range(12))
    assert failing is None, failing.summary()
    assert all(o.status == "ok" and not o.races for o in outs)


def test_deadlock_schedule_is_reported_not_hung():
    class DeadlockHarness:
        name = "deadlock"
        watch = ()

        def setup(self):
            return {"a": threading.Lock(), "b": threading.Lock()}

        def threads(self, ctx):
            a, b = ctx["a"], ctx["b"]

            def ab():
                with a:
                    with b:
                        pass

            def ba():
                with b:
                    with a:
                        pass

            return [("ab", ab), ("ba", ba)]

        def check(self, ctx):
            pass

    outs, failing = sweep(lambda: DeadlockHarness(), range(64))
    assert failing is not None, "AB/BA inversion never deadlocked in 64 seeds"
    assert failing.status == "deadlock", failing.summary()
    assert "holds" in failing.error


# -- the four real harnesses --------------------------------------------------


@pytest.mark.parametrize(
    "cls",
    [DevicePlaneHarness, ProofPlaneHarness, AdmissionQuotasHarness,
     SchedulerHarness, PipelinedCommitHarness, PipelineObsHarness,
     QuorumCollectorHarness],
    ids=lambda c: c.name,
)
def test_real_harness_seeded_sweep(cls):
    outs, failing = sweep(lambda: cls(), range(8))
    assert failing is None, failing.summary()
    assert all(o.status == "ok" and not o.races for o in outs)


def test_real_harnesses_registry_complete():
    assert set(HARNESSES) == {
        "device-plane", "proof-singleflight", "admission-quotas",
        "scheduler-commit", "pipelined-commit", "pipeline-obs",
        "qc-collector", "fleet-obs", "torn-quorum",
    }


def test_real_harness_runs_are_deterministic():
    a = Explorer(seed=5).run(SchedulerHarness())
    b = Explorer(seed=5).run(SchedulerHarness())
    assert (a.digest, a.status) == (b.digest, b.status)


# -- raceguard over the real DevicePlane under the lockorder recorder ---------


def test_raceguard_plane_traffic_under_instrumented_cv_is_clean():
    """The plane's _cv is now an explicit package RLock: with the lockorder
    factory installed (conftest), raceguard sees every stats access under
    a non-empty lockset — the suite-wide FISCO_RACEGUARD=1 contract."""
    from fisco_bcos_tpu.analysis import lockorder
    from fisco_bcos_tpu.analysis.lockorder import RECORDER
    from fisco_bcos_tpu.device.plane import DevicePlane

    if not lockorder._installed:
        pytest.skip("lockorder factory not installed (FISCO_LOCKORDER=0)")
    guard = RaceGuard(lockset_fn=RECORDER.held_sites)
    guard.watch(DevicePlane, ("requests", "items", "dispatches"))
    try:
        plane = DevicePlane(window_ms=0, autostart=False)
        assert isinstance(plane._cv._lock, lockorder.InstrumentedRLock)

        def submit():
            plane.submit("x", None, 1, lambda reqs: [r.n for r in reqs])

        threads = [threading.Thread(target=submit) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        import time

        with plane._cv:
            picked = plane._pick_ready_locked(time.perf_counter())
        assert picked is not None
        plane._dispatch(picked[0], picked[1])
    finally:
        guard.unwatch_all()
    assert guard.report() == [], guard.report()

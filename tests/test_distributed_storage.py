"""Sharded distributed storage backend — the TiKV-analog.

Reference: bcos-storage/bcos-storage/TiKVStorage.cpp (distributed KV regions,
2PC prepare/commit, connection-loss switch handler :582).
"""

import jax

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from fisco_bcos_tpu.service import StorageService  # noqa: E402
from fisco_bcos_tpu.service.rpc import ServiceRemoteError  # noqa: E402
from fisco_bcos_tpu.storage import MemoryStorage  # noqa: E402
from fisco_bcos_tpu.storage.distributed import DistributedStorage  # noqa: E402
from fisco_bcos_tpu.storage.entry import Entry  # noqa: E402
from fisco_bcos_tpu.storage.interfaces import TwoPCParams  # noqa: E402
from fisco_bcos_tpu.storage.state_storage import StateStorage  # noqa: E402


def _cluster(n):
    backings = [MemoryStorage() for _ in range(n)]
    svcs = [StorageService(b) for b in backings]
    for s in svcs:
        s.start()
    dist = DistributedStorage([(s.host, s.port) for s in svcs], timeout=5.0)
    return backings, svcs, dist


def test_rows_spread_and_read_back():
    backings, svcs, dist = _cluster(3)
    try:
        n = 64
        for i in range(n):
            dist.set_row("t", b"k%02d" % i, Entry().set(b"v%02d" % i))
        # every row reads back through routing
        for i in range(n):
            assert dist.get_row("t", b"k%02d" % i).get() == b"v%02d" % i
        # and the placement actually used more than one shard
        per_shard = [len(b.get_primary_keys("t")) for b in backings]
        assert sum(per_shard) == n and sum(1 for c in per_shard if c) >= 2
        # merged scans see the union
        assert len(dist.get_primary_keys("t")) == n
    finally:
        for s in svcs:
            s.stop()


def test_2pc_commits_atomically_across_shards():
    backings, svcs, dist = _cluster(3)
    try:
        writes = StateStorage()
        for i in range(32):
            writes.set_row("acct", b"u%02d" % i, Entry().set(b"%d" % i))
        params = TwoPCParams(number=7)
        dist.prepare(params, writes)
        # nothing visible before commit
        assert all(b.get_row("acct", b"u00") is None for b in backings)
        dist.commit(params)
        for i in range(32):
            assert dist.get_row("acct", b"u%02d" % i).get() == b"%d" % i
    finally:
        for s in svcs:
            s.stop()


def test_rollback_drops_staged_writes():
    backings, svcs, dist = _cluster(2)
    try:
        writes = StateStorage()
        writes.set_row("t", b"x", Entry().set(b"staged"))
        dist.prepare(TwoPCParams(number=3), writes)
        dist.rollback(TwoPCParams(number=3))
        dist.commit(TwoPCParams(number=3))  # committing nothing is a no-op
        assert dist.get_row("t", b"x") is None
    finally:
        for s in svcs:
            s.stop()


def test_shard_loss_fires_switch_and_recovers():
    backings, svcs, dist = _cluster(2)
    fired = []
    dist.set_switch_handler(lambda: fired.append(1))
    try:
        for i in range(16):
            dist.set_row("t", b"r%02d" % i, Entry().set(b"ok"))
        # kill one shard: routed reads to it fail and fire the switch seam
        svcs[1].stop()
        with pytest.raises(ServiceRemoteError):
            for i in range(16):
                dist.get_row("t", b"r%02d" % i)
        assert fired
        # restart the shard on the same endpoint with the same disk
        svc1b = StorageService(
            backings[1], host=svcs[1].host, port=svcs[1].port
        )
        svc1b.start()
        svcs[1] = svc1b
        for i in range(16):
            assert dist.get_row("t", b"r%02d" % i).get() == b"ok"
    finally:
        for s in svcs:
            s.stop()


class _Writes:
    def __init__(self, rows):
        self.rows = rows

    def traverse(self):
        yield from self.rows


def test_2pc_recovery_rolls_forward_past_primary_commit():
    """TiKV lock-resolution semantics: a crash AFTER the primary commit
    (the witness is durable) but before the secondaries' commits must roll
    the stragglers FORWARD on recovery, not back — the coordinator had
    passed the point of no return."""
    backings, svcs, dist = _cluster(3)
    try:
        rows = [("t", b"rf%02d" % i, Entry().set(b"v%d" % i)) for i in range(24)]
        params = TwoPCParams(number=7)
        dist.prepare(params, _Writes(rows))
        # crash between phases: only the PRIMARY commits (witness lands)
        backings[0].commit(params)
        assert backings[1].pending_numbers() or backings[2].pending_numbers()

        dist.mark_needs_recovery()
        dist.recover_in_flight_if_needed()
        for _t, k, e in rows:
            got = dist.get_row("t", k)
            assert got is not None and got.get() == e.get(), k
        for b in backings:
            assert b.pending_numbers() == []
    finally:
        for s in svcs:
            s.stop()


def test_2pc_recovery_rolls_back_without_witness():
    """A crash BEFORE the primary commit leaves no witness: every shard's
    staged slot rolls back and the data never becomes visible."""
    backings, svcs, dist = _cluster(3)
    try:
        rows = [("t", b"rb%02d" % i, Entry().set(b"x")) for i in range(24)]
        params = TwoPCParams(number=9)
        dist.prepare(params, _Writes(rows))
        dist.mark_needs_recovery()
        dist.recover_in_flight_if_needed()
        for _t, k, _e in rows:
            assert dist.get_row("t", k) is None
        for b in backings:
            assert b.pending_numbers() == []
    finally:
        for s in svcs:
            s.stop()


def test_sqlite_prepared_slot_survives_restart(tmp_path):
    """Durable prewrite (TiKV persists locks): a prepared slot must survive
    the participant process restarting, so recovery can still roll it
    forward."""
    from fisco_bcos_tpu.storage import SQLiteStorage

    db = str(tmp_path / "part.db")
    st = SQLiteStorage(db)
    st.prepare(TwoPCParams(number=3), _Writes([("t", b"k", Entry().set(b"v"))]))
    assert st.pending_numbers() == [3]
    st.close()
    st2 = SQLiteStorage(db)  # "restarted process"
    assert st2.pending_numbers() == [3]
    assert st2.get_row("t", b"k") is None  # staged, not visible
    st2.commit(TwoPCParams(number=3))
    assert st2.get_row("t", b"k").get() == b"v"
    assert st2.pending_numbers() == []
    st2.close()


def test_armed_recovery_must_not_roll_back_the_block_being_committed():
    """Regression: a transient outage between prepare(N) and commit(N)
    arms recovery; the commit(N) that follows must NOT let the recovery
    pass roll N back (it has no witness yet) — that would commit empty
    slots and silently lose the block."""
    backings, svcs, dist = _cluster(3)
    try:
        rows = [("t", b"cx%02d" % i, Entry().set(b"v%d" % i)) for i in range(16)]
        params = TwoPCParams(number=5)
        dist.prepare(params, _Writes(rows))
        dist.mark_needs_recovery()  # transient blip after prepare
        dist.commit(params)
        for _t, k, e in rows:
            got = dist.get_row("t", k)
            assert got is not None and got.get() == e.get(), k
    finally:
        for s in svcs:
            s.stop()


def test_witness_rows_are_retired():
    """Only a bounded number of commit-witness rows may survive: committing
    N retires N-1's witness, and rollback retires its own."""
    backings, svcs, dist = _cluster(2)
    try:
        for n in (1, 2, 3):
            dist.prepare(
                TwoPCParams(number=n), _Writes([("t", b"w%d" % n, Entry().set(b"x"))])
            )
            dist.commit(TwoPCParams(number=n))
        live = [
            k for k in backings[0].get_primary_keys("s_2pc_witness")
        ] + [
            k for k in backings[1].get_primary_keys("s_2pc_witness")
        ]
        assert live == [b"commit-3"], live
        # rollback retires its own witness even after a partial commit
        dist.prepare(
            TwoPCParams(number=4), _Writes([("t", b"w4", Entry().set(b"x"))])
        )
        backings[0].commit(TwoPCParams(number=4))  # partial: primary only
        dist.rollback(TwoPCParams(number=4))
        live = [
            k for b in backings for k in b.get_primary_keys("s_2pc_witness")
        ]
        assert b"commit-4" not in live
    finally:
        for s in svcs:
            s.stop()


def _leg_ms(op: str, shard: int) -> tuple[float, int]:
    """(sum of ms, count) of one shard's 2PC leg on the process registry."""
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    hist = REGISTRY._histograms.get("fisco_storage_shard_2pc_ms")  # no create
    if hist is None:
        return 0.0, 0
    key = (("op", op), ("shard", str(shard)))
    _cum, total, count = hist.snapshot().get(key, ((), 0.0, 0))
    return total, count


def test_shard_attribution_pins_an_injected_slow_shard():
    """A FaultPlan-delayed shard must show up as THAT shard's prepare leg
    in ``fisco_storage_shard_2pc_ms{op,shard}`` — the attribution the flat
    2PC stage time can't provide."""
    from fisco_bcos_tpu.resilience import (
        FaultPlan,
        clear_fault_plan,
        install_fault_plan,
    )
    from fisco_bcos_tpu.storage.distributed import SHARD_2PC_BUCKETS_MS
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    backings, svcs, dist = _cluster(3)
    try:
        rows = [
            ("t", b"sh%02d" % i, Entry().set(b"v%d" % i)) for i in range(24)
        ]
        before = {
            (op, i): _leg_ms(op, i)
            for op in ("prepare", "commit") for i in range(3)
        }
        install_fault_plan(
            FaultPlan(seed=19).rule(
                "delay", "send", f"{svcs[1].port}/prepare", delay_ms=80
            )
        )
        try:
            dist.prepare(TwoPCParams(number=4), _Writes(rows))
            dist.commit(TwoPCParams(number=4))
        finally:
            clear_fault_plan()
        took = {}
        for k, (ms0, n0) in before.items():
            ms1, n1 = _leg_ms(*k)
            assert n1 - n0 == 1, f"one observation a shard leg: {k} {n0}->{n1}"
            took[k] = ms1 - ms0
        delayed = took[("prepare", 1)]
        others = max(took[("prepare", 0)], took[("prepare", 2)])
        assert delayed >= 60.0, f"delayed shard not attributed: {took}"
        assert delayed > others + 40.0, (delayed, others)
        hist = REGISTRY.histogram("fisco_storage_shard_2pc_ms")
        assert hist.buckets == tuple(float(b) for b in SHARD_2PC_BUCKETS_MS)
        # the 2PC itself landed: every row reads back through routing
        for t, k, e in rows:
            assert dist.get_row(t, k).get() == e.get()
    finally:
        for s in svcs:
            s.stop()

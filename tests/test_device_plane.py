"""DevicePlane: coalescer mechanics, priority lanes, shape-bucket
bit-identity, passthrough mode, and the host-vs-device cutover env.

The bit-identity property (ISSUE 3 acceptance): routing a batch through the
plane — merged with strangers, bucket-padded, sliced back — must produce
byte-for-byte the same outputs as the pre-plane direct dispatch, across
ragged batch sizes including all-invalid and empty batches. A divergence
would fork a plane-routed node from a passthrough node.
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager

import numpy as np
import pytest

from fisco_bcos_tpu.crypto import admission
from fisco_bcos_tpu.crypto.ref import ecdsa as ref
from fisco_bcos_tpu.crypto.ref.keccak import keccak256
from fisco_bcos_tpu.crypto.suite import ecdsa_suite, sm_suite
from fisco_bcos_tpu.device.plane import (
    DevicePlane,
    device_lane,
    get_plane,
    plane_enabled,
    plane_route,
)


@contextmanager
def _env(name: str, value: str | None):
    old = os.environ.get(name)
    if value is None:
        os.environ.pop(name, None)
    else:
        os.environ[name] = value
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = old


def _signed(payloads, base=0xA11CE):
    sigs = []
    for i, p in enumerate(payloads):
        d = base + 31337 * i
        r, s, v = ref.ecdsa_sign(keccak256(p), d)
        sigs.append(r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([v]))
    return np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(-1, 65).copy()


def _admit_both_modes(payloads, sigs):
    """(direct, planed) admit_batch outputs for the same inputs."""
    with _env("FISCO_DEVICE_PLANE", "0"):
        direct = admission.admit_batch(payloads, sigs)
    with _env("FISCO_DEVICE_PLANE", None):
        planed = admission.admit_batch(payloads, sigs)
    return direct, planed


# -- bit-identity across ragged batch sizes ----------------------------------


@pytest.mark.parametrize("n", [1, 7, 63, 100, 1000])
def test_plane_matches_direct_admission_ragged(n):
    payloads = [b"rag-%d " % i + b"x" * (i * 13 % 97) for i in range(n)]
    sigs = _signed(payloads)
    if n >= 3:
        sigs[2, :64] = 0  # one structurally-invalid lane
    direct, planed = _admit_both_modes(payloads, sigs)
    for a, b in zip(direct, planed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert planed[1].sum() == (n - 1 if n >= 3 else n)


def test_plane_matches_direct_all_invalid_and_empty():
    payloads = [b"inv-%d" % i for i in range(5)]
    sigs = np.zeros((5, 65), dtype=np.uint8)  # every lane garbage
    direct, planed = _admit_both_modes(payloads, sigs)
    for a, b in zip(direct, planed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not planed[1].any()

    empty_sigs = np.zeros((0, 65), dtype=np.uint8)
    direct, planed = _admit_both_modes([], empty_sigs)
    for a, b in zip(direct, planed):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype


def test_plane_matches_direct_device_leg(monkeypatch):
    """Force the device program on both legs (the bucketed/padded path the
    plane exists for) — outputs must still match the direct dispatch."""
    monkeypatch.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")
    for n in (3, 9):
        payloads = [b"dev-%d " % i + b"y" * (i * 7 % 50) for i in range(n)]
        sigs = _signed(payloads, base=0xBEEF)
        if n > 4:
            sigs[4, 32:64] = 0
        direct, planed = _admit_both_modes(payloads, sigs)
        for a, b in zip(direct, planed):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plane_matches_direct_batch_verify_and_recover():
    suite = ecdsa_suite()
    impl = suite.signature_impl
    kp = impl.generate_keypair(secret=0x5EED)
    msgs = [b"verify-%d" % i for i in range(7)]
    hashes = np.frombuffer(
        b"".join(keccak256(m) for m in msgs), np.uint8
    ).reshape(-1, 32)
    sigs = np.frombuffer(
        b"".join(impl.sign(kp, keccak256(m)) for m in msgs), np.uint8
    ).reshape(-1, 65).copy()
    pubs = np.frombuffer(kp.pub * len(msgs), np.uint8).reshape(-1, 64)
    sigs[3, :32] = 0  # invalid lane lowers a bit, never raises

    with _env("FISCO_DEVICE_PLANE", "0"):
        ok_direct = impl.batch_verify(hashes, pubs, sigs)
        rec_direct = impl.batch_recover(hashes, sigs)
    ok_planed = impl.batch_verify(hashes, pubs, sigs)
    rec_planed = impl.batch_recover(hashes, sigs)
    np.testing.assert_array_equal(ok_direct, ok_planed)
    np.testing.assert_array_equal(rec_direct[0], rec_planed[0])
    np.testing.assert_array_equal(rec_direct[1], rec_planed[1])
    assert ok_planed.sum() == len(msgs) - 1


def test_plane_matches_direct_sm_suite():
    suite = sm_suite()
    impl = suite.signature_impl
    kp = impl.generate_keypair(secret=0x51712)
    msgs = [b"sm-%d" % i for i in range(4)]
    hashes = np.frombuffer(
        b"".join(suite.hash(m) for m in msgs), np.uint8
    ).reshape(-1, 32)
    sigs = np.frombuffer(
        b"".join(impl.sign(kp, suite.hash(m)) for m in msgs), np.uint8
    ).reshape(-1, 128).copy()
    sigs[1, :32] = 0
    pubs = np.frombuffer(kp.pub * len(msgs), np.uint8).reshape(-1, 64)
    with _env("FISCO_DEVICE_PLANE", "0"):
        ok_direct = impl.batch_verify(hashes, pubs, sigs)
        rec_direct = impl.batch_recover(hashes, sigs)
    ok_planed = impl.batch_verify(hashes, pubs, sigs)
    rec_planed = impl.batch_recover(hashes, sigs)
    np.testing.assert_array_equal(ok_direct, ok_planed)
    np.testing.assert_array_equal(rec_direct[0], rec_planed[0])
    np.testing.assert_array_equal(rec_direct[1], rec_planed[1])


def test_plane_hash_matches_reference():
    suite = ecdsa_suite()
    msgs = [b"h%d" % i * (i + 1) for i in range(9)]
    out = suite.hash_batch(msgs)
    for m, d in zip(msgs, out):
        assert bytes(d) == keccak256(m)
    # async form resolves to the same digests, repeatably
    resolve = suite.hash_batch_async(msgs)
    np.testing.assert_array_equal(resolve(), out)
    np.testing.assert_array_equal(resolve(), out)


def test_hash_batch_async_overlaps_before_sync():
    """Two async dispatches queued before either resolver is called — the
    satellite fix: the default used to run eagerly, syncing per caller."""
    suite = ecdsa_suite()
    r1 = suite.hash_batch_async([b"overlap-a", b"overlap-b"])
    r2 = suite.hash_batch_async([b"overlap-c"])
    assert bytes(r2()[0]) == keccak256(b"overlap-c")
    out1 = r1()
    assert bytes(out1[0]) == keccak256(b"overlap-a")
    assert bytes(out1[1]) == keccak256(b"overlap-b")


# -- scheduler mechanics (standalone plane, no device) ------------------------


def _echo_exec(calls):
    def run(reqs):
        calls.append([r.n for r in reqs])
        merged = []
        for r in reqs:
            merged.extend(r.payload)
        out, lo = [], 0
        for r in reqs:
            out.append(merged[lo : lo + r.n])
            lo += r.n
        return out

    return run


def test_coalescer_merges_up_to_high_water():
    """Two sub-water requests sit in the window; the submit that crosses
    high water triggers ONE merged dispatch with correct per-request
    slices."""
    plane = DevicePlane(window_ms=60_000, high_water=8, starvation_ms=60_000)
    calls: list[list[int]] = []
    f1 = plane.submit("echo", ["a", "b", "c"], 3, _echo_exec(calls))
    f2 = plane.submit("echo", ["d", "e"], 2, _echo_exec(calls))
    f3 = plane.submit("echo", ["f", "g", "h"], 3, _echo_exec(calls))  # total 8
    assert f1.result(timeout=10) == ["a", "b", "c"]
    assert f2.result(timeout=10) == ["d", "e"]
    assert f3.result(timeout=10) == ["f", "g", "h"]
    assert calls == [[3, 2, 3]]  # one dispatch, three requests
    assert plane.coalesce_ratio() == 3.0
    assert plane.stats()["merged_requests"] == 3


def test_window_expiry_dispatches_partial_batch():
    plane = DevicePlane(window_ms=10, high_water=1 << 30, starvation_ms=60_000)
    calls: list[list[int]] = []
    f = plane.submit("echo", ["x"], 1, _echo_exec(calls))
    assert f.result(timeout=10) == ["x"]  # window, not high water, fired it
    assert calls == [[1]]


def test_priority_lanes_and_starvation_ordering():
    """consensus > admission > sync among ready groups; a starved group
    preempts lane order (oldest first) so sync can never be parked
    forever."""
    import time

    plane = DevicePlane(window_ms=0, autostart=False)
    dummy = _echo_exec([])
    with device_lane("sync"):
        plane.submit("op.sync", ["s"], 1, dummy)
    time.sleep(0.002)
    with device_lane("consensus"):
        plane.submit("op.cons", ["c"], 1, dummy)
    plane.submit("op.adm", ["a"], 1, dummy)  # default lane: admission

    now = time.perf_counter()
    plane.starvation_ms = 60_000  # nothing starved: lane order decides
    op, reqs, _def = plane._pick_ready_locked(now)
    assert op == "op.cons" and reqs[0].lane == "consensus"
    plane._pending[op] = reqs  # put it back

    plane.starvation_ms = 0.001  # everything starved: oldest group first
    op, _reqs, _def = plane._pick_ready_locked(now)
    assert op == "op.sync"


def test_executor_exception_propagates_to_all_futures():
    plane = DevicePlane(window_ms=60_000, high_water=2, starvation_ms=60_000)

    def boom(reqs):
        raise ValueError("device fell over")

    f1 = plane.submit("boom", [1], 1, boom)
    f2 = plane.submit("boom", [2], 1, boom)  # crosses high water
    with pytest.raises(ValueError):
        f1.result(timeout=10)
    with pytest.raises(ValueError):
        f2.result(timeout=10)
    # the worker survives a failed dispatch (two submits cross high water —
    # mutating plane knobs after submit would race the worker's readiness
    # check)
    ok1 = plane.submit("echo", ["z"], 1, _echo_exec([]))
    ok2 = plane.submit("echo", ["w"], 1, _echo_exec([]))
    assert ok1.result(timeout=10) == ["z"]
    assert ok2.result(timeout=10) == ["w"]


def test_concurrent_submitters_coalesce_and_stay_correct():
    """Threaded callers racing into the same op merge without corrupting
    each other's slices (the actual flood topology: RPC + consensus + sync
    threads sharing the plane)."""
    plane = DevicePlane(window_ms=25, high_water=1 << 30, starvation_ms=60_000)
    calls: list[list[int]] = []
    results: dict[int, list] = {}
    barrier = threading.Barrier(4)

    def worker(tag: int):
        payload = [f"{tag}-{j}" for j in range(tag + 1)]
        barrier.wait()
        results[tag] = plane.submit(
            "echo", payload, len(payload), _echo_exec(calls)
        ).result(timeout=20)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for tag in range(4):
        assert results[tag] == [f"{tag}-{j}" for j in range(tag + 1)]
    assert sum(len(c) for c in calls) == 4  # every request dispatched once


# -- passthrough + policy env -------------------------------------------------


def test_plane_disabled_is_passthrough():
    suite = ecdsa_suite()
    with _env("FISCO_DEVICE_PLANE", "0"):
        assert not plane_enabled() and not plane_route()
        before = get_plane().stats()["requests"]
        suite.hash_batch([b"direct-1", b"direct-2"])
        payloads = [b"direct-adm"]
        admission.admit_batch(payloads, _signed(payloads))
        assert get_plane().stats()["requests"] == before  # nothing enqueued


def test_device_min_batch_env(monkeypatch):
    from fisco_bcos_tpu.crypto import suite as suite_mod

    # pretend the backend is an accelerator so the threshold is decisive
    monkeypatch.setattr(suite_mod, "device_backend_is_cpu", lambda: False)
    monkeypatch.delenv("FISCO_DEVICE_MIN_BATCH", raising=False)
    assert suite_mod.device_min_batch() == suite_mod._SMALL_BATCH
    assert suite_mod.use_native_batch(10)
    monkeypatch.setenv("FISCO_DEVICE_MIN_BATCH", "4")
    assert not suite_mod.use_native_batch(10)
    assert suite_mod.use_native_batch(3)
    monkeypatch.setenv("FISCO_DEVICE_MIN_BATCH", "not-a-number")
    assert suite_mod.device_min_batch() == suite_mod._SMALL_BATCH


def test_bucket_ladder_bounds_shapes():
    from fisco_bcos_tpu.ops.hash_common import bucket_batch, bucket_ladder

    ladder = bucket_ladder(1000)
    assert ladder[-1] >= 1000
    # every bucket a ragged flood ≤ 1000 can produce is on the ladder
    for n in (1, 7, 63, 100, 999, 1000):
        assert bucket_batch(n) in ladder
    assert ladder == sorted(set(ladder))


# -- group-fair deficit-round-robin (ISSUE 6) --------------------------------


def _drr_plane(**kw):
    kw.setdefault("window_ms", 0)
    kw.setdefault("autostart", False)
    plane = DevicePlane(**kw)
    plane.starvation_ms = 60_000
    return plane


def _noop_exec(reqs):
    return [None] * len(reqs)


def test_single_group_selection_unchanged():
    """Fairness must cost the common (single-tenant) case nothing: the
    whole queue merges into one dispatch, beyond high water, no deferral."""
    plane = _drr_plane(high_water=100)
    from fisco_bcos_tpu.device.plane import device_group

    with device_group("g0"):
        for i in range(5):
            plane.submit("op", [i], 60, _noop_exec)  # 300 items >> high_water
    import time

    op, taken, deferred = plane._pick_ready_locked(time.perf_counter())
    assert op == "op" and len(taken) == 5 and deferred == []


def test_drr_bounds_abusive_group_and_serves_victim():
    """A saturating single-group flood cannot fill every dispatch: the
    victim's late-arriving request rides the FIRST dispatch and the
    abuser's surplus is deferred (counted per group)."""
    import time

    from fisco_bcos_tpu.device.plane import device_group

    plane = _drr_plane(high_water=200)
    with device_group("abuser"):
        for i in range(10):
            plane.submit("op", [i], 100, _noop_exec)  # 1000 items queued
    with device_group("victim"):
        plane.submit("op", ["v"], 50, _noop_exec)

    op, taken, deferred = plane._pick_ready_locked(time.perf_counter())
    groups_taken = [r.group for r in taken]
    assert "victim" in groups_taken  # served in the first dispatch
    items = sum(r.n for r in taken)
    assert items <= 200 + 100  # cap respected (one request may overshoot)
    assert deferred and all(r.group == "abuser" for r in deferred)
    # the abuser's backlog went back to the queue front, oldest first
    assert plane._pending["op"][0].group == "abuser"
    assert [r.payload for r in plane._pending["op"] if r.group == "abuser"] == [
        [i] for i in range(10) if [i] not in [r.payload for r in taken]
    ]


def test_drr_drains_abuser_eventually_and_resets_deficit():
    import time

    from fisco_bcos_tpu.device.plane import device_group

    plane = _drr_plane(high_water=150)
    with device_group("a"):
        for i in range(6):
            plane.submit("op", [i], 50, _noop_exec)
    with device_group("b"):
        plane.submit("op", ["b0"], 50, _noop_exec)
    seen_payloads = []
    for _ in range(10):
        picked = plane._pick_ready_locked(time.perf_counter())
        if picked is None:
            break
        _op, taken, _deferred = picked
        seen_payloads.extend(r.payload for r in taken)
    assert len(seen_payloads) == 7  # nothing lost, nothing duplicated
    # b drained inside a contended dispatch: its credit is forfeited there;
    # a drained via the single-group fast path, which keeps no DRR books
    assert "b" not in plane._deficit


def test_drr_weights_shift_share():
    """A weight-2 group gets ~2x the items of a weight-1 group in the
    capped first dispatch."""
    import time

    from fisco_bcos_tpu.device.plane import device_group

    plane = _drr_plane(high_water=300)
    plane.group_weights = {"gold": 2.0, "basic": 1.0}
    plane.group_quantum = 50
    with device_group("gold"):
        for i in range(20):
            plane.submit("op", [f"g{i}"], 25, _noop_exec)
    with device_group("basic"):
        for i in range(20):
            plane.submit("op", [f"b{i}"], 25, _noop_exec)
    _op, taken, deferred = plane._pick_ready_locked(time.perf_counter())
    gold = sum(r.n for r in taken if r.group == "gold")
    basic = sum(r.n for r in taken if r.group == "basic")
    assert deferred  # contention actually happened
    assert gold >= 1.5 * basic, (gold, basic)


def test_drr_respects_lane_priority_between_groups():
    """Within the merged queue, a consensus-lane request from ANY group is
    selected before admission-lane bulk, whatever the DRR state."""
    import time

    from fisco_bcos_tpu.device.plane import device_group

    plane = _drr_plane(high_water=100)
    with device_group("bulk"):
        for i in range(5):
            plane.submit("op", [i], 60, _noop_exec)
    with device_group("chain"), device_lane("consensus"):
        plane.submit("op", ["qc"], 10, _noop_exec)
    _op, taken, _deferred = plane._pick_ready_locked(time.perf_counter())
    assert taken[0].lane == "consensus" and taken[0].group == "chain"


def test_drr_deferred_requests_still_dispatch_through_worker():
    """End-to-end through the live worker thread: every future resolves
    even when fairness splits the queue across several dispatches."""
    from fisco_bcos_tpu.device.plane import device_group

    plane = DevicePlane(window_ms=0, high_water=120, autostart=True)
    calls: list[int] = []

    def count_exec(reqs):
        calls.append(sum(r.n for r in reqs))
        return [r.payload for r in reqs]

    futures = []
    with device_group("a"):
        for i in range(8):
            futures.append(plane.submit("op", i, 50, count_exec))
    with device_group("b"):
        futures.append(plane.submit("op", "vb", 50, count_exec))
    outs = [f.result(timeout=30) for f in futures]
    assert outs == list(range(8)) + ["vb"]
    assert sum(calls) == 450  # every item dispatched exactly once

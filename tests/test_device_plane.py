"""DevicePlane and the dispatch seam: coalescer mechanics, priority lanes,
shape-bucket bit-identity, the seam's entry / policy / legs, and the
host-vs-device cutover env.

The bit-identity property (ISSUE 3 acceptance): routing a batch through the
plane — merged with strangers, bucket-padded, sliced back — must produce
byte-for-byte what the plain reference (crypto/ref) says, across ragged
batch sizes including all-invalid and empty batches. A divergence would
fork a node from its peers.
"""

from __future__ import annotations

import functools
import importlib
import threading

import numpy as np
import pytest

from fisco_bcos_tpu import native_bind
from fisco_bcos_tpu.crypto import admission
from fisco_bcos_tpu.crypto.ref import ecdsa as ref
from fisco_bcos_tpu.crypto.ref.keccak import keccak256
from fisco_bcos_tpu.crypto.suite import Ed25519Crypto, ecdsa_suite, sm_suite
from fisco_bcos_tpu.device import dispatch as dispatch_mod
from fisco_bcos_tpu.device.plane import DevicePlane, device_lane, get_plane

N_KEYS = 16  # distinct signers of a corpus: the reference derives each key once


@functools.cache
def _pub(d: int) -> bytes:
    x, y = ref.privkey_to_pubkey(ref.SECP256K1, d)
    return x.to_bytes(32, "big") + y.to_bytes(32, "big")


def _secret(i: int, base: int) -> int:
    return base + 31337 * (i % N_KEYS)


def _signed(payloads, base=0xA11CE):
    sigs = []
    for i, p in enumerate(payloads):
        r, s, v = ref.ecdsa_sign(keccak256(p), _secret(i, base))
        sigs.append(r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([v]))
    return np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(-1, 65).copy()


def _rsv(sig):
    sig = bytes(sig)
    return int.from_bytes(sig[:32], "big"), int.from_bytes(sig[32:64], "big"), sig[64]


def _assert_admitted(got, payloads, sigs, base=0xA11CE, broken=()):
    """admit_batch's answer held to crypto/ref lane by lane: an intact lane
    recovers its signer's key (derived by the reference from the secret) and
    that key's address; a lane the test broke is one the reference's recover
    refuses; every lane carries the reference's digest."""
    senders, ok, pubs, digests = (np.asarray(a) for a in got)
    assert len(ok) == len(payloads)
    for i, p in enumerate(payloads):
        assert bytes(digests[i]) == keccak256(p), i
        if i in broken:
            assert ref.ecdsa_recover(keccak256(p), *_rsv(sigs[i])) is None
            assert not ok[i], i
        else:
            want = _pub(_secret(i, base))
            assert ok[i] and bytes(pubs[i]) == want, i
            assert bytes(senders[i]) == keccak256(want)[12:], i


# -- bit-identity across ragged batch sizes ----------------------------------


@pytest.mark.parametrize("n", [1, 7, 63, 100, 1000])
def test_plane_admission_matches_reference_ragged(n):
    payloads = [b"rag-%d " % i + b"x" * (i * 13 % 97) for i in range(n)]
    sigs = _signed(payloads)
    broken = (2,) if n >= 3 else ()
    for lane in broken:
        sigs[lane, :64] = 0  # one structurally-invalid lane
    before = get_plane().stats()["requests"]
    got = admission.admit_batch(payloads, sigs)
    assert get_plane().stats()["requests"] == before + 1
    _assert_admitted(got, payloads, sigs, broken=broken)


def test_plane_admission_all_invalid_and_empty():
    payloads = [b"inv-%d" % i for i in range(5)]
    sigs = np.zeros((5, 65), dtype=np.uint8)  # every lane garbage
    got = admission.admit_batch(payloads, sigs)
    _assert_admitted(got, payloads, sigs, broken=range(5))

    # nothing to queue: the body runs inline and answers the empty shapes
    before = get_plane().stats()["requests"]
    got = admission.admit_batch([], np.zeros((0, 65), dtype=np.uint8))
    assert get_plane().stats()["requests"] == before
    assert [np.asarray(a).shape for a in got] == [(0, 20), (0,), (0, 64), (0, 32)]
    assert np.asarray(got[1]).dtype == bool
    assert {np.asarray(got[i]).dtype for i in (0, 2, 3)} == {np.dtype(np.uint8)}


def test_plane_admission_device_leg_matches_reference(monkeypatch):
    """Force the device program (the bucketed/padded path the plane exists
    for) — its lanes are still the reference's."""
    monkeypatch.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")
    for n in (3, 9):
        payloads = [b"dev-%d " % i + b"y" * (i * 7 % 50) for i in range(n)]
        sigs = _signed(payloads, base=0xBEEF)
        broken = (4,) if n > 4 else ()
        for lane in broken:
            sigs[lane, 32:64] = 0
        got = admission.admit_batch(payloads, sigs)
        _assert_admitted(got, payloads, sigs, base=0xBEEF, broken=broken)


def _signature_corpus(suite, secret, n, tag):
    impl = suite.signature_impl
    kp = impl.generate_keypair(secret=secret)
    msgs = [b"%s-%d" % (tag, i) for i in range(n)]
    hashes = np.frombuffer(
        b"".join(suite.hash(m) for m in msgs), np.uint8
    ).reshape(-1, 32)
    sigs = np.frombuffer(
        b"".join(impl.sign(kp, suite.hash(m)) for m in msgs), np.uint8
    ).reshape(n, impl.sig_len).copy()
    pubs = np.frombuffer(kp.pub * n, np.uint8).reshape(-1, 64)
    return kp, hashes, pubs, sigs


def test_plane_batch_verify_and_recover_match_reference():
    impl = ecdsa_suite().signature_impl
    kp, hashes, pubs, sigs = _signature_corpus(ecdsa_suite(), 0x5EED, 7, b"verify")
    sigs[3, :32] = 0  # invalid lane lowers a bit, never raises
    point = (kp.pub_x, kp.pub_y)
    want = [
        ref.ecdsa_verify(bytes(h), *_rsv(s)[:2], point) for h, s in zip(hashes, sigs)
    ]
    ok = impl.batch_verify(hashes, pubs, sigs)
    rec_pubs, rec_ok = impl.batch_recover(hashes, sigs)
    assert list(ok) == want == [i != 3 for i in range(7)]
    for i, (h, s) in enumerate(zip(hashes, sigs)):
        got = ref.ecdsa_recover(bytes(h), *_rsv(s))
        assert bool(rec_ok[i]) == (got is not None), i
        if got is not None:
            assert bytes(rec_pubs[i]) == got[0].to_bytes(32, "big") + got[1].to_bytes(32, "big")
    assert bytes(rec_pubs[0]) == kp.pub and not rec_pubs[3].any()


def test_plane_sm_suite_matches_reference():
    impl = sm_suite().signature_impl
    kp, hashes, pubs, sigs = _signature_corpus(sm_suite(), 0x51712, 4, b"sm")
    sigs[1, :32] = 0
    point = (kp.pub_x, kp.pub_y)
    want = [
        ref.sm2_verify(bytes(h), *_rsv(s)[:2], point) for h, s in zip(hashes, sigs)
    ]
    ok = impl.batch_verify(hashes, pubs, sigs)
    rec_pubs, rec_ok = impl.batch_recover(hashes, sigs)
    assert list(ok) == list(rec_ok) == want == [True, False, True, True]
    for i in range(4):
        assert bytes(rec_pubs[i]) == (kp.pub if want[i] else b"\x00" * 64)


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


# -- the dispatch seam: entry, policy, legs -------------------------------------


def test_seam_call_on_the_plane_worker_runs_inline():
    """The re-entry rule: an executor that calls back into a seam (ed25519
    batch_recover → batch_verify) must not queue behind itself. The nested
    call runs its body on the worker and the shared plane sees no request."""
    impl = Ed25519Crypto()
    kps = [impl.generate_keypair(secret=0xED00 + i) for i in range(3)]
    hashes = [bytes([i + 1]) * 32 for i in range(3)]
    sigs = [impl.sign(kp, h) for kp, h in zip(kps, hashes)]
    sigs[1] = bytes([sigs[1][0] ^ 1]) + sigs[1][1:]
    seen = []

    def nested(reqs):
        seen.append(threading.current_thread().name)
        before = get_plane().stats()["requests"]
        out = [impl.batch_recover(*r.payload) for r in reqs]
        assert get_plane().stats()["requests"] == before  # nothing enqueued
        return out

    plane = DevicePlane(window_ms=0)
    pubs, ok = plane.submit("nested", (hashes, sigs), 3, nested).result(timeout=60)
    assert seen == ["device-plane"]
    assert list(ok) == [True, False, True]
    assert bytes(pubs[0]) == kps[0].pub and not pubs[1].any()
    # the same call from a caller's thread does queue
    before = get_plane().stats()["requests"]
    assert list(impl.batch_recover(hashes, sigs)[1]) == [True, False, True]
    assert get_plane().stats()["requests"] == before + 1


def test_policy_is_asked_once_with_the_merged_size(monkeypatch):
    """Two 200-item requests coalesced on a pretend accelerator ride the
    device leg, though either alone is under the 256 cutover: the policy is
    applied to the merged batch, once per dispatch."""
    monkeypatch.setattr(dispatch_mod, "device_backend_is_cpu", lambda: False)
    monkeypatch.delenv("FISCO_DEVICE_MIN_BATCH", raising=False)
    asked = []
    policy = dispatch_mod.use_native_batch

    def recording(n, label=""):
        asked.append((n, label))
        return policy(n, label)

    monkeypatch.setattr(dispatch_mod, "use_native_batch", recording)
    op = dispatch_mod.BatchOp(
        "pretend", "pretend",
        device=lambda x, tags: (x * 3, x + 1),
        native=lambda x, tags: (x * 2, x),
    )
    a, b = np.arange(200), np.arange(200, 400)
    assert dispatch_mod.run_legs(op, 200, a, ["a"] * 200)[0][1] == 2  # alone: native
    asked.clear()

    plane = DevicePlane(window_ms=60_000, high_water=400, starvation_ms=60_000)
    run = dispatch_mod._merge_exec(op)
    f1 = plane.submit("pretend", (a, ["a"] * 200), 200, run)
    f2 = plane.submit("pretend", (b, ["b"] * 200), 200, run)  # crosses high water
    (a3, a1), (b3, b1) = f1.result(timeout=10), f2.result(timeout=10)
    assert asked == [(400, "pretend")]
    np.testing.assert_array_equal(a3, a * 3)
    np.testing.assert_array_equal(b3, b * 3)
    np.testing.assert_array_equal(b1, b + 1)
    assert len(a1) == 200


def _failing(calls):
    """A device program that records the attempt and raises."""

    def device(*_a, **_k):
        calls.append("device")
        raise RuntimeError("forced device failure")

    return device


def _case_signature(suite_fn, method, device_fn_name, native_fn_name):
    """A signature batch op: its device program (an ops host wrapper) fails,
    its native batch loop answers None."""

    def build(monkeypatch, calls):
        suite = suite_fn()
        impl = suite.signature_impl
        ops_mod = importlib.import_module(f"fisco_bcos_tpu.ops.{impl.name}")
        monkeypatch.setattr(ops_mod, device_fn_name, _failing(calls))
        monkeypatch.setattr(native_bind, native_fn_name, lambda *a, **k: None)
        kp, hashes, pubs, sigs = _signature_corpus(suite, 0xFA11, 3, b"leg")
        sigs[1, :32] = 0
        if method == "batch_verify":
            return lambda: np.asarray(impl.batch_verify(hashes, pubs, sigs))
        return lambda: np.asarray(impl.batch_recover(hashes, sigs)[1])

    return build


def _case_ed25519(monkeypatch, calls):
    from fisco_bcos_tpu.ops import ed25519 as ed_ops

    impl = Ed25519Crypto()
    kps = [impl.generate_keypair(secret=0xED10 + i) for i in range(3)]
    hashes = [bytes([i + 7]) * 32 for i in range(3)]
    sigs = [impl.sign(kp, h) for kp, h in zip(kps, hashes)]
    sigs[1] = bytes([sigs[1][0] ^ 1]) + sigs[1][1:]
    pubs = [kp.pub for kp in kps]
    monkeypatch.setattr(ed_ops, "verify_batch", _failing(calls))
    monkeypatch.setattr(native_bind, "load", lambda: None)  # the native leg: None
    return lambda: np.asarray(impl.batch_verify(hashes, pubs, sigs))


def _case_admission(suite_fn, step_name):
    """Fused admission: the jitted step fails. The SM body's native loop
    answers None (no library); the secp body's host fallback IS the native
    loop, so there the policy override picks the device leg instead."""

    def build(monkeypatch, calls):
        suite = suite_fn()
        impl = suite.signature_impl
        monkeypatch.setattr(admission, step_name, _failing(calls))
        if impl.name == "sm2":
            monkeypatch.setattr(native_bind, "load", lambda: None)
        else:
            monkeypatch.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")
        kp = impl.generate_keypair(secret=0xAD17)
        payloads = [b"leg-%d" % i for i in range(3)]
        sigs = np.frombuffer(
            b"".join(impl.sign(kp, suite.hash(p)) for p in payloads), np.uint8
        ).reshape(3, impl.sig_len).copy()
        sigs[1, :32] = 0
        admit = suite.fused_admission()
        return lambda: np.asarray(admit(payloads, sigs)[1])

    return build


@pytest.mark.parametrize(
    "label,build",
    [
        ("secp256k1_verify", _case_signature(ecdsa_suite, "batch_verify", "verify_batch", "secp256k1_verify_batch")),
        ("secp256k1_recover", _case_signature(ecdsa_suite, "batch_recover", "recover_batch", "secp256k1_recover_batch")),
        ("sm2_verify", _case_signature(sm_suite, "batch_verify", "verify_batch", "sm2_verify_batch")),
        ("sm2_recover", _case_signature(sm_suite, "batch_recover", "recover_batch", "sm2_verify_batch")),
        ("ed25519_verify", _case_ed25519),
        ("admission", _case_admission(ecdsa_suite, "admission_step_packed")),
        ("admission", _case_admission(sm_suite, "sm_admission_step_packed")),
    ],
    ids=["secp256k1_verify", "secp256k1_recover", "sm2_verify", "sm2_recover",
         "ed25519_verify", "admission", "admission_sm"],
)
def test_failing_device_leg_is_answered_by_the_host_loop(label, build, monkeypatch):
    """Every op with a host loop: a native leg that answers None falls
    through to the device; a device program that raises is answered, lane
    for lane, by the host loop and counted; after the breaker's two failures
    the device is no longer tried and the leg reads ``host_fallback``."""
    from fisco_bcos_tpu.observability.device import LEDGER
    from fisco_bcos_tpu.resilience import CircuitBreaker
    from fisco_bcos_tpu.resilience.breaker import HealthRegistry
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    # a private breaker + registry + failure book: the process-wide ones
    # must not trip
    breaker = CircuitBreaker(
        "device-crypto", failure_threshold=2, reset_timeout=60.0,
        critical=False, registry=HealthRegistry(),
    )
    monkeypatch.setattr(dispatch_mod, "_DEVICE_BREAKER", breaker)
    monkeypatch.setattr(LEDGER, "_failures", {})
    calls: list[str] = []
    call = build(monkeypatch, calls)

    def legs():
        series = REGISTRY.counters_matching(
            f'fisco_device_dispatch_path_total{{op="{label}",'
        )
        return {
            path: series.get(
                f'fisco_device_dispatch_path_total{{op="{label}",path="{path}"}}', 0.0
            )
            for path in ("native", "device", "host_fallback")
        }

    before = legs()
    for _ in range(3):
        assert list(call()) == [True, False, True]
    after = legs()
    assert calls == ["device", "device"]  # the third call never tried it
    assert breaker.state == "open"
    assert {k: after[k] - before[k] for k in after} == {
        "native": 0, "device": 2, "host_fallback": 1,
    }
    assert LEDGER.failures()[label]["count"] == 2
    assert "forced device failure" in LEDGER.failures()[label]["last_error"]


def test_device_min_batch_env(monkeypatch):
    # pretend the backend is an accelerator so the threshold is decisive
    monkeypatch.setattr(dispatch_mod, "device_backend_is_cpu", lambda: False)
    monkeypatch.delenv("FISCO_DEVICE_MIN_BATCH", raising=False)
    monkeypatch.delenv("FISCO_FORCE_DEVICE_ADMISSION", raising=False)
    assert dispatch_mod.device_min_batch() == dispatch_mod._SMALL_BATCH == 256
    assert dispatch_mod.use_native_batch(10)
    monkeypatch.setenv("FISCO_DEVICE_MIN_BATCH", "4")
    assert not dispatch_mod.use_native_batch(10)
    assert dispatch_mod.use_native_batch(3)
    assert not dispatch_mod.use_native_batch(0)
    monkeypatch.setenv("FISCO_DEVICE_MIN_BATCH", "not-a-number")
    assert dispatch_mod.device_min_batch() == dispatch_mod._SMALL_BATCH
    # the admission override is the admission ops' alone
    monkeypatch.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")
    assert not dispatch_mod.use_native_batch(10, "admission")
    assert dispatch_mod.use_native_batch(10, "secp256k1_verify")


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

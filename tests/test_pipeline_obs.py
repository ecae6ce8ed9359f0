"""Pipeline observatory tests (ISSUE 9): stage state machine with an
injected clock, blocked-on attribution, watermark ring bounds, profiler
determinism via injected frame snapshots, the /pipeline + /profile
endpoints on both deployment splits, near-zero overhead when disabled,
the /trace/tx miss-reason contract and the flood-window stage aggregation."""

from __future__ import annotations

import json
import threading
import urllib.request

import pytest

from fisco_bcos_tpu.observability import critical_path, profiler
from fisco_bcos_tpu.observability.pipeline import (
    _NOOP,
    PIPELINE,
    PipelineRecorder,
    pipeline_doc,
)


def make_clock(step: float = 1.0):
    """Deterministic clock: each read advances by ``step`` seconds."""
    state = {"t": 0.0}
    lock = threading.Lock()

    def clock():
        with lock:
            state["t"] += step
            return state["t"]

    return clock


def rec_for_test(**kw):
    kw.setdefault("clock", make_clock())
    kw.setdefault("enabled", True)
    kw.setdefault("emit_metrics", False)
    return PipelineRecorder(**kw)


# -- stage state machine ------------------------------------------------------


def test_busy_interval_accounting_with_injected_clock():
    rec = rec_for_test()
    with rec.busy("admission"):
        pass
    snap = rec.snapshot()["admission"]
    # enter reads the clock once, exit once: exactly one tick of busy time
    assert snap["busy_ms"] == 1000.0
    assert snap["intervals"] == 1
    assert snap["state"] == "idle"
    assert snap["active_threads"] == 0


def test_blocked_inside_busy_attributes_and_subtracts():
    rec = rec_for_test()
    with rec.busy("admission"):
        with rec.blocked("device_plane"):
            pass
    snap = rec.snapshot()["admission"]
    # busy wall = 3 ticks (enter..exit), blocked = 1 tick, so busy = 2
    assert snap["blocked_ms"] == {"device_plane": 1000.0}
    assert snap["busy_ms"] == 2000.0
    assert snap["blocked_intervals"] == 1


def test_blocked_without_ambient_stage_is_noop_and_explicit_stage_works():
    rec = rec_for_test()
    assert rec.blocked("whatever") is _NOOP
    with rec.blocked("io", stage="commit"):
        pass
    snap = rec.snapshot()["commit"]
    assert snap["blocked_ms"] == {"io": 1000.0}
    assert snap["busy_ms"] == 0.0


def test_nested_blocked_on_same_stage_keeps_outer_attribution():
    """A wait reached from INSIDE an already-blocked region (a plane wait
    under a 2PC leg) must not flip the state machine twice: the outer
    edge keeps the time, and the thread counts return to zero."""
    rec = rec_for_test()
    with rec.busy("commit"):
        with rec.blocked("2pc_prepare"):
            with rec.blocked("device_plane"):
                pass
    snap = rec.snapshot()["commit"]
    assert snap["blocked_intervals"] == 1
    assert "device_plane" not in snap["blocked_ms"]
    assert snap["blocked_ms"]["2pc_prepare"] > 0
    assert snap["state"] == "idle"
    assert snap["active_threads"] == 0 and snap["blocked_threads"] == 0
    # a DIFFERENT stage's blocked nests fine (consensus -> execute shape)
    with rec.busy("a"):
        with rec.blocked("x"):
            with rec.blocked("y", stage="b"):
                pass
    assert rec.snapshot()["b"]["blocked_ms"]["y"] > 0


def test_nested_same_stage_busy_is_reentrant_noop():
    rec = rec_for_test()
    with rec.busy("execute"):
        with rec.busy("execute"):  # the executor seam under the scheduler's
            pass
    snap = rec.snapshot()["execute"]
    assert snap["intervals"] == 1
    assert snap["busy_ms"] == 1000.0  # inner pair consumed no clock reads


def test_sticky_marks_model_the_sealer_loop():
    rec = rec_for_test()
    rec.mark_blocked("sealer", "consensus_quorum")
    # re-marking the same edge keeps t0 (no churn across idle ticks)
    rec.mark_blocked("sealer", "consensus_quorum")
    snap = rec.snapshot()["sealer"]
    assert snap["state"] == "blocked"
    assert snap["blocked_on"] == "consensus_quorum"
    assert snap["blocked_ms"]["consensus_quorum"] > 0  # open interval shown
    with rec.busy("sealer"):  # sealing closes the sticky interval
        pass
    snap = rec.snapshot()["sealer"]
    assert snap["blocked_intervals"] == 1
    assert snap["intervals"] == 1
    rec.mark_idle("sealer")
    assert rec.snapshot()["sealer"]["state"] == "idle"


def test_utilization_window_replay():
    clock = make_clock(1.0)
    rec = PipelineRecorder(clock=clock, enabled=True, emit_metrics=False)
    with rec.busy("execute"):
        pass
    # busy from t=2..3 (enter/exit reads), snapshot reads more ticks; the
    # lifetime ratio and the windowed replay must both land in (0, 1)
    u_all = rec.utilization("execute", window_s=1e9)
    assert 0.0 < u_all < 1.0
    assert rec.utilization("missing-stage") == 0.0


def test_multithreaded_stage_counts_thread_ms_and_returns_to_idle():
    rec = PipelineRecorder(enabled=True, emit_metrics=False)
    barrier = threading.Barrier(3)

    def work():
        barrier.wait()
        for _ in range(3):
            with rec.busy("admission"):
                with rec.blocked("device_plane"):
                    pass

    threads = [threading.Thread(target=work) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = rec.snapshot()["admission"]
    assert snap["intervals"] == 9
    assert snap["blocked_intervals"] == 9
    assert snap["active_threads"] == 0 and snap["blocked_threads"] == 0
    assert snap["state"] == "idle"


def test_timeline_ring_is_bounded():
    rec = rec_for_test(timeline_cap=8)
    for _ in range(50):
        with rec.busy("s"):
            pass
    tl = rec.timelines()["s"]
    assert len(tl) <= 8


# -- watermarks ---------------------------------------------------------------


def test_watermark_rings_are_bounded_and_expand_dict_probes():
    rec = rec_for_test(watermark_cap=16)
    rec.add_probe("pool", lambda: 3)
    rec.add_probe("lanes", lambda: {"consensus": 1, "sync": 2})
    assert not rec.add_probe("pool", lambda: 99)  # first registration wins
    for _ in range(40):
        rec.sample_once()
    marks = rec.watermarks()
    assert set(marks) == {"pool", "lanes.consensus", "lanes.sync"}
    assert marks["pool"]["n"] == 16  # ring bound, not 40
    assert marks["pool"]["last"] == 3.0
    assert marks["lanes.sync"]["max"] == 2.0


def test_failing_probe_is_dropped_after_eight_strikes():
    rec = rec_for_test()

    def bad():
        raise RuntimeError("probe died")

    rec.add_probe("bad", bad)
    rec.add_probe("good", lambda: 1)
    for _ in range(10):
        rec.sample_once()
    marks = rec.watermarks()
    assert "bad" not in marks and marks["good"]["n"] == 10
    with rec._lock:
        assert "bad" not in rec._probes  # dropped, not retried forever


def test_bound_method_probes_do_not_pin_their_node_and_name_is_reusable():
    """A node's probes are held through weakrefs: tearing the node down
    (garbage collection) removes the probe at the next sweep and frees
    the name for the replacement node — the in-process restart path."""
    import gc

    class FakePool:
        def depth(self):
            return 11

    rec = rec_for_test()
    pool = FakePool()
    assert rec.add_probe("pool", pool.depth)
    rec.sample_once()
    assert rec.watermarks()["pool"]["last"] == 11.0
    # a LIVE probe still refuses a replacement (first registration wins)
    assert not rec.add_probe("pool", FakePool().depth)
    del pool
    gc.collect()
    rec.sample_once()  # dead probe detected and removed immediately
    with rec._lock:
        assert "pool" not in rec._probes
    # the restarted node re-claims the name
    pool2 = FakePool()
    assert rec.add_probe("pool", pool2.depth)
    rec.sample_once()
    assert rec.watermarks()["pool"]["n"] == 2


def test_counter_events_render_chrome_counter_shape():
    rec = rec_for_test()
    rec.add_probe("pool", lambda: 5)
    rec.sample_once()
    (ev,) = rec.counter_events()
    assert ev["ph"] == "C" and ev["name"] == "queue.pool"
    assert ev["args"] == {"depth": 5.0}


# -- disabled = near-zero overhead --------------------------------------------


def test_disabled_recorder_is_shared_noop_and_allocates_nothing():
    rec = PipelineRecorder(enabled=False)
    assert rec.busy("x") is _NOOP
    assert rec.blocked("y", stage="x") is _NOOP
    rec.mark_blocked("x", "y")
    rec.mark_idle("x")
    assert not rec.add_probe("p", lambda: 1)
    rec.sample_once()
    rec.ensure_sampler()
    assert rec.snapshot() == {}
    assert rec.watermarks() == {}
    with rec._lock:
        assert rec._stages == {} and rec._probes == {}
    assert rec._sampler is None


def test_env_switch_disables_the_recorder(monkeypatch):
    monkeypatch.setenv("FISCO_PIPELINE_OBS", "0")
    rec = PipelineRecorder(emit_metrics=False)
    assert not rec.enabled
    assert rec.busy("x") is _NOOP


# -- profiler -----------------------------------------------------------------


class _FakeFrame:
    def __init__(self, name, filename, back=None):
        class _Code:
            pass

        self.f_code = _Code()
        self.f_code.co_name = name
        self.f_code.co_filename = filename
        self.f_lineno = 1
        self.f_back = back


def _fake_stack():
    root = _FakeFrame("loop", "/repo/fisco_bcos_tpu/node/runtime.py")
    mid = _FakeFrame("execute", "/repo/fisco_bcos_tpu/scheduler/scheduler.py", root)
    leaf = _FakeFrame("verify", "/repo/fisco_bcos_tpu/crypto/suite.py", mid)
    return leaf


def test_profiler_fold_is_deterministic_with_injected_frames():
    p1 = profiler.SamplingProfiler(emit_metrics=False)
    p2 = profiler.SamplingProfiler(emit_metrics=False)
    for p in (p1, p2):
        for _ in range(3):
            p.take_sample({101: _fake_stack()})
    assert p1.collapsed() == p2.collapsed()
    key = (
        "fisco_bcos_tpu/node/runtime.py:loop;"
        "fisco_bcos_tpu/scheduler/scheduler.py:execute;"
        "fisco_bcos_tpu/crypto/suite.py:verify"
    )
    assert p1.collapsed() == {key: 3}
    assert p1.collapsed_text() == f"{key} 3"
    # self time lands on the LEAF only
    assert p1.self_times() == {"fisco_bcos_tpu/crypto/suite.py:verify": 3}


def test_profiler_package_filter_drops_stdlib_only_threads():
    p = profiler.SamplingProfiler(emit_metrics=False)
    stdlib = _FakeFrame("wait", "/usr/lib/python3/threading.py")
    p.take_sample({1: stdlib, 2: _fake_stack()})
    assert p.samples == 1
    assert p.stack_samples == 1  # the stdlib-only thread folded to nothing
    rep = p.report()
    assert rep["self_top"][0]["func"] == "fisco_bcos_tpu/crypto/suite.py:verify"
    assert rep["self_top"][0]["pct"] == 100.0


def test_profiler_mixed_stack_keeps_package_frames_only():
    pkg = _FakeFrame("work", "/repo/fisco_bcos_tpu/txpool/txpool.py")
    std_on_top = _FakeFrame("sha256", "/usr/lib/python3/hashlib.py", pkg)
    p = profiler.SamplingProfiler(emit_metrics=False)
    p.take_sample({7: std_on_top})
    assert p.collapsed() == {"fisco_bcos_tpu/txpool/txpool.py:work": 1}


def test_live_profile_endpoint_body_and_single_flight():
    doc = profiler.profile(seconds=0.1, hz=200)
    assert doc["samples"] > 0
    assert "collapsed" in doc and "self_top" in doc
    assert doc["overhead"]["duty_cycle"] < 1.0
    # single-flight: a concurrent request reports busy instead of doubling
    # the sampling tax
    got = {}
    with profiler._PROFILE_LOCK:
        got = profiler.profile(seconds=0.1)
    assert got.get("error") == "profiler busy"


# -- endpoints: Air form ------------------------------------------------------


def test_pipeline_and_profile_endpoints_over_air_http():
    from fisco_bcos_tpu.rpc.http_server import RpcHttpServer

    with PIPELINE.busy("admission"):
        with PIPELINE.blocked("device_plane"):
            pass
    server = RpcHttpServer(
        impl=None, port=0, pipeline=pipeline_doc, profile=profiler.profile
    )
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(f"{base}/pipeline", timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("application/json")
            doc = json.loads(resp.read())
        assert doc["enabled"] is True
        adm = doc["stages"]["admission"]
        assert adm["blocked_ms"]["device_plane"] >= 0.0
        with urllib.request.urlopen(
            f"{base}/profile?seconds=0.1", timeout=30
        ) as resp:
            prof = json.loads(resp.read())
        assert prof["samples"] > 0
    finally:
        server.stop()


# -- endpoints: Pro split -----------------------------------------------------


def test_pipeline_and_profile_endpoints_over_pro_split():
    """The RPC front door serves /pipeline and /profile by forwarding to
    the node core's facade (RemoteTelemetry) — the same path /metrics and
    /trace take in the split deployment."""
    from fisco_bcos_tpu.service.rpc_service import RpcFacade, RpcService

    with PIPELINE.busy("execute"):
        pass
    facade = RpcFacade(impl=None)
    facade.start()
    rpc = RpcService(facade.host, facade.port)
    try:
        base = f"http://127.0.0.1:{rpc.port}"
        rpc.start()
        with urllib.request.urlopen(f"{base}/pipeline", timeout=10) as resp:
            doc = json.loads(resp.read())
        assert doc["enabled"] is True
        assert "execute" in doc["stages"]
        with urllib.request.urlopen(
            f"{base}/profile?seconds=0.1", timeout=30
        ) as resp:
            prof = json.loads(resp.read())
        assert prof["samples"] > 0 and "collapsed" in prof
    finally:
        rpc.stop()
        facade.stop()


def test_remote_telemetry_pipeline_degrades_on_dead_facade():
    from fisco_bcos_tpu.service.rpc_service import RemoteTelemetry

    rt = RemoteTelemetry("127.0.0.1", 1, timeout=0.5)
    try:
        doc = rt.pipeline()
        assert doc["enabled"] is False and "error" in doc
        prof = rt.profile(0.1)
        assert "error" in prof
    finally:
        rt.close()


# -- /trace/tx miss reasons ---------------------------------------------------


def test_trace_tx_miss_reasons_unknown_unsampled_evicted(monkeypatch):
    critical_path.reset()
    try:
        doc = critical_path.trace_tx("ab" * 32)
        assert doc["found"] is False and doc["reason"] == "unknown"

        # head-sampled-out txs are remembered as unsampled
        critical_path.note_txs([b"\x01" * 32], None)
        doc = critical_path.trace_tx((b"\x01" * 32).hex())
        assert doc["reason"] == "unsampled"
        assert "FISCO_TRACE_SAMPLE" in doc["detail"]

        # index eviction is remembered as evicted
        monkeypatch.setattr(critical_path, "_TX_CAP", 2)
        from fisco_bcos_tpu.observability.tracer import TraceContext

        ctx = TraceContext(trace_id=7, span_id=8, sampled=True)
        hashes = [bytes([i]) * 32 for i in range(2, 6)]
        critical_path.note_txs(hashes, ctx)
        doc = critical_path.trace_tx(hashes[0].hex())
        assert doc["found"] is False and doc["reason"] == "evicted"
        # the surviving tail is still found
        assert critical_path.collect(hashes[-1].hex())["found"] is True
    finally:
        critical_path.reset()


# -- flood-window stage aggregation -------------------------------------------


def test_aggregate_stage_self_ms_dedups_shared_block_spans():
    from fisco_bcos_tpu.observability.tracer import TRACER

    critical_path.reset()
    TRACER.clear()
    try:
        ctx_a = TRACER.new_root_context("a")
        ctx_b = TRACER.new_root_context("b")
        block_ctx = TRACER.new_root_context("block")
        t0 = 1000.0
        TRACER.record("txpool.submit", t0, 0.010, ctx=ctx_a)
        TRACER.record("txpool.submit", t0, 0.010, ctx=ctx_b)
        # one block-stage span shared by both txs: must count ONCE
        TRACER.record(
            "scheduler.execute_block", t0 + 0.02, 0.050, ctx=block_ctx, block=9
        )
        critical_path.note_txs([b"\xaa" * 32], ctx_a)
        critical_path.note_txs([b"\xbb" * 32], ctx_b)
        critical_path.note_sealed([b"\xaa" * 32, b"\xbb" * 32], 9)
        critical_path.note_block_trace(9, block_ctx.trace_id)
        critical_path.note_committed([b"\xaa" * 32, b"\xbb" * 32], 9)
        agg = critical_path.aggregate_stage_self_ms()
        assert agg["txs"] == 2
        assert agg["stages"]["txpool.submit"]["count"] == 2
        assert agg["stages"]["scheduler.execute_block"]["count"] == 1
        assert agg["stages"]["scheduler.execute_block"]["self_ms"] == 50.0
    finally:
        critical_path.reset()
        TRACER.clear()


# -- the wired pipeline end to end (single-node chain) ------------------------


@pytest.mark.slow
def test_live_chain_records_stage_occupancy_and_edges():
    from fisco_bcos_tpu.codec.abi import ABICodec
    from fisco_bcos_tpu.crypto.suite import ecdsa_suite
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig
    from fisco_bcos_tpu.node import Node, NodeConfig
    from fisco_bcos_tpu.protocol.transaction import TransactionFactory

    suite = ecdsa_suite()
    codec = ABICodec(suite.hash)
    kp = suite.signature_impl.generate_keypair(secret=0x0B51)
    node = Node(
        NodeConfig(genesis=GenesisConfig(consensus_nodes=[ConsensusNode(kp.pub)])),
        keypair=kp,
    )
    fac = TransactionFactory(suite)
    sender = suite.signature_impl.generate_keypair(secret=0x0B52)
    txs = [
        fac.create_signed(
            sender,
            chain_id="chain0",
            group_id="group0",
            block_limit=500,
            nonce=f"obs-{i}",
            to=DAG_TRANSFER_ADDRESS,
            input=codec.encode_call("userAdd(string,uint256)", f"o{i}", 1),
        )
        for i in range(4)
    ]
    assert all(r.status == 0 for r in node.txpool.submit_batch(txs))
    assert node.sealer.seal_and_submit()
    assert node.block_number() == 1
    PIPELINE.sample_once()
    doc = pipeline_doc()
    stages = doc["stages"]
    for expect in ("admission", "sealer", "consensus", "execute", "commit"):
        assert expect in stages, sorted(stages)
        assert stages[expect]["busy_ms"] > 0 or stages[expect]["blocked_ms"]
    edges = {
        (s, on) for s, v in stages.items() for on in v["blocked_ms"]
    }
    assert ("commit", "2pc_prepare") in edges
    assert ("consensus", "execute") in edges
    assert "txpool.pending" in doc["watermarks"]

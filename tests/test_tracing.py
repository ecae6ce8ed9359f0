"""End-to-end transaction-lifecycle tracing (ISSUE 4).

Covers the upgraded trace semantics (128-bit trace ids, explicit span
parentage, contextvars propagation, W3C-style traceparent across the
service split), span links through the device-plane coalescer, head-based
sampling + drop accounting, exemplars, retry-attempt spans under fault
injection, and the ``/trace/tx/<hash>`` critical-path stitcher over a
Pro-split deployment.
"""

import sys

sys.path.insert(0, "tests")

import jax

jax.config.update("jax_platforms", "cpu")

import json  # noqa: E402
import threading  # noqa: E402
import urllib.error  # noqa: E402
import urllib.request  # noqa: E402

import pytest  # noqa: E402

from fisco_bcos_tpu.observability import TRACER, TraceContext, Tracer  # noqa: E402
from fisco_bcos_tpu.observability import critical_path  # noqa: E402
from fisco_bcos_tpu.resilience import (  # noqa: E402
    FaultPlan,
    clear_fault_plan,
    install_fault_plan,
)
from fisco_bcos_tpu.resilience.retry import RetryPolicy, mark_idempotent  # noqa: E402
from fisco_bcos_tpu.service.rpc import ServiceClient, ServiceServer  # noqa: E402


@pytest.fixture(autouse=True)
def _clean():
    clear_fault_plan()
    yield
    clear_fault_plan()


# ---------------------------------------------------------------------------
# core trace semantics
# ---------------------------------------------------------------------------


def test_spans_get_real_ids_and_parentage():
    tr = Tracer(capacity=16)
    with tr.span("outer") as outer:
        with tr.span("outer") as inner:  # SAME name: ids must disambiguate
            pass
    recs = tr.spans()
    assert len(recs) == 2
    by_id = {r.span_id: r for r in recs}
    inner_rec = by_id[inner.ctx.span_id]
    outer_rec = by_id[outer.ctx.span_id]
    assert inner_rec.trace_id == outer_rec.trace_id != 0
    assert inner_rec.parent_id == outer_rec.span_id
    assert outer_rec.parent_id is None
    assert inner_rec.span_id != outer_rec.span_id
    # chrome export carries the ids; the name stays only as a display label
    doc = tr.export_chrome()
    args = {e["args"]["span_id"]: e["args"] for e in doc["traceEvents"]}
    iargs = args[f"{inner_rec.span_id:016x}"]
    assert iargs["parent"] == "outer"  # label, ambiguous by design
    assert iargs["parent_id"] == f"{outer_rec.span_id:016x}"  # the truth
    assert iargs["trace_id"] == f"{outer_rec.trace_id:032x}"


def test_traceparent_round_trip_and_malformed():
    ctx = TraceContext(trace_id=0xABC, span_id=0x123, sampled=True)
    tp = ctx.traceparent()
    assert tp == f"00-{0xabc:032x}-{0x123:016x}-01"
    back = TraceContext.from_traceparent(tp)
    assert (back.trace_id, back.span_id, back.sampled) == (0xABC, 0x123, True)
    off = TraceContext(1, 2, sampled=False).traceparent()
    assert off.endswith("-00")
    assert TraceContext.from_traceparent(off).sampled is False
    for bad in ("", "garbage", "00-zz-11-01", "00-1-2-01", None):
        assert TraceContext.from_traceparent(bad) is None


def test_attach_carries_context_across_threads():
    tr = Tracer(capacity=16)
    with tr.span("root") as root:
        ctx = root.ctx
        done = threading.Event()

        def worker():
            # a worker thread starts context-free; attach() re-parents
            with tr.attach(ctx):
                with tr.span("child"):
                    pass
            done.set()

        threading.Thread(target=worker).start()
        assert done.wait(5)
    child = next(r for r in tr.spans() if r.name == "child")
    assert child.trace_id == ctx.trace_id
    assert child.parent_id == ctx.span_id


def test_noop_span_set_contract():
    tr = Tracer(capacity=4, enabled=False)
    sp = tr.span("x", a=1)
    assert sp.ctx is None
    # documented trap: item assignment lands in a throwaway dict per access
    sp.attrs["k"] = "v"
    assert "k" not in sp.attrs
    # the supported API is set(), a no-op returning the span
    assert sp.set(k="v") is sp
    with sp:
        pass
    assert tr.spans() == []


def test_sampling_zero_is_noop_and_counted():
    tr = Tracer(capacity=16, sample_rate=0.0)
    for _ in range(5):
        with tr.span("s"):
            pass
    assert tr.spans() == []
    assert tr.drop_counts()["sampled"] == 5
    # retroactive records under no ambient context are sampled out too
    assert tr.record("r", 0.0, 1.0) is None
    assert tr.drop_counts()["sampled"] == 6


def test_unsampled_context_propagates_and_suppresses_children():
    tr = Tracer(capacity=16, sample_rate=1.0)
    off = TraceContext(7, 8, sampled=False)
    with tr.attach(off):
        with tr.span("child"):  # suppressed: upstream said no
            pass
        assert tr.record("retro", 0.0, 0.1) is None
    assert tr.spans() == []
    assert tr.drop_counts()["sampled"] == 2


def test_ring_eviction_is_counted():
    tr = Tracer(capacity=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.spans()) == 4
    assert tr.spans()[-1].name == "s9"
    assert tr.drop_counts()["ring_evict"] == 6


def test_record_returns_ctx_and_honors_parent_and_links():
    tr = Tracer(capacity=16)
    root = tr.new_root_context("root")
    other = tr.new_root_context("other")
    ctx = tr.record(
        "phase", 1.0, 0.5, parent_ctx=root, links=[other], block=3
    )
    assert ctx is not None and ctx.trace_id == root.trace_id
    (rec,) = tr.spans()
    assert rec.parent_id == root.span_id
    assert rec.links == ((other.trace_id, other.span_id),)
    assert rec.attrs["block"] == 3


def test_exemplars_render_only_under_openmetrics():
    from fisco_bcos_tpu.utils.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.observe("lat_ms", 42.0, help="latency", exemplar="deadbeef")
    reg.observe("lat_ms", 41.0)  # no exemplar: line stays bare
    om = reg.render(openmetrics=True)
    line = next(
        ln for ln in om.splitlines() if ln.startswith('lat_ms_bucket{le="50"}')
    )
    assert '# {trace_id="deadbeef"} 42' in line
    bare = next(
        ln for ln in om.splitlines() if ln.startswith('lat_ms_bucket{le="0"}')
    )
    assert "#" not in bare
    assert om.splitlines()[-1] == "# EOF"
    # the classic 0.0.4 exposition must stay exemplar-free — the plain
    # Prometheus text parser rejects a mid-line '#'
    classic = reg.render()
    assert "# {" not in classic and "# EOF" not in classic


def test_metrics_endpoint_negotiates_openmetrics_exemplars():
    from fisco_bcos_tpu.rpc.http_server import RpcHttpServer
    from fisco_bcos_tpu.utils.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.observe("neg_ms", 10.0, help="negotiated", exemplar="feedface")
    server = RpcHttpServer(impl=None, port=0, metrics=reg)
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}/metrics"
        with urllib.request.urlopen(base, timeout=5) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            assert b"# {" not in resp.read()
        req = urllib.request.Request(
            base, headers={"Accept": "application/openmetrics-text"}
        )
        with urllib.request.urlopen(req, timeout=5) as resp:
            assert resp.headers["Content-Type"].startswith(
                "application/openmetrics-text"
            )
            assert b'# {trace_id="feedface"}' in resp.read()
    finally:
        server.stop()


def test_zero_capacity_ring_drops_without_crashing():
    tr = Tracer(capacity=0)
    with tr.span("s"):
        pass
    assert tr.spans() == []
    assert tr.drop_counts()["ring_evict"] == 1


def test_dominant_stage_judged_by_self_time_not_wrapper_duration():
    # pbft.execute_and_checkpoint WRAPS scheduler.execute_block and always
    # outlasts it; dominant must name the stage doing the work, not the
    # umbrella (docs/observability.md worked example)
    span = dict(pid=1, tid=1, trace_id="a" * 32, links=[], attrs={})
    doc = critical_path.analyze(
        {
            "found": True,
            "spans": [
                {**span, "name": "pbft.execute_and_checkpoint", "wall": 0.0,
                 "dur": 0.0319, "span_id": "1" * 16, "parent_id": None},
                {**span, "name": "scheduler.execute_block", "wall": 0.0001,
                 "dur": 0.0317, "span_id": "2" * 16, "parent_id": "1" * 16},
            ],
        }
    )
    assert doc["dominant"] == "scheduler.execute_block"
    assert doc["dominant_ms"] == 31.7
    wrapper = next(
        s for s in doc["stages"] if s["name"] == "pbft.execute_and_checkpoint"
    )
    assert wrapper["self_ms"] == 0.2  # dur minus its child


def test_note_sealed_dedups_shared_batch_context():
    tr_ctx = TRACER.new_root_context("batch")
    hashes = [bytes([i]) * 32 for i in range(5)]
    for h in hashes:
        critical_path.note_tx(h, tr_ctx)  # batch admission: shared ctx
    before = len([r for r in TRACER.spans() if r.name == "txpool.pool_wait"])
    ctxs = critical_path.note_sealed(hashes, number=777)
    after = len([r for r in TRACER.spans() if r.name == "txpool.pool_wait"])
    assert len(ctxs) == 1  # one link, not five
    assert after - before == 1  # one pool_wait span, not five


# ---------------------------------------------------------------------------
# trace context across the service split (+ fault injection)
# ---------------------------------------------------------------------------


def _echo_server():
    srv = ServiceServer("echo")
    srv.register("ping", lambda payload: payload)
    mark_idempotent("ping")
    srv.start()
    return srv


def test_traceparent_crosses_service_rpc():
    srv = _echo_server()
    client = ServiceClient(srv.host, srv.port, timeout=5.0)
    try:
        with TRACER.span("caller.root") as root:
            assert client.call("ping", b"hi") == b"hi"
        svc = [
            r
            for r in TRACER.spans()
            if r.name == "svc.echo.ping" and r.trace_id == root.ctx.trace_id
        ]
        assert svc, "server-side span did not join the caller's trace"
        assert svc[0].parent_id == root.ctx.span_id
    finally:
        client.close()
        srv.stop()


def test_retry_attempts_become_child_spans_under_dropped_frames():
    srv = _echo_server()
    client = ServiceClient(
        srv.host,
        srv.port,
        timeout=5.0,
        retry=RetryPolicy(max_attempts=3, base_delay=0.01, seed=7),
    )
    # drop the FIRST reply on the client's recv path: attempt 0 sees a dead
    # connection, attempt 1 redials and succeeds
    install_fault_plan(
        FaultPlan(seed=5).drop("recv", f"{srv.port}/ping", count=1)
    )
    try:
        with TRACER.span("faulted.root") as root:
            assert client.call("ping", b"x") == b"x"
        mine = [r for r in TRACER.spans() if r.trace_id == root.ctx.trace_id]
        names = {r.name for r in mine}
        assert "retry.attempt" in names, "retry left a mystery gap"
        retry = next(r for r in mine if r.name == "retry.attempt")
        assert retry.attrs["attempt"] == 1
        assert retry.parent_id == root.ctx.span_id
        # the successful attempt's server span stitched into the same trace
        assert "svc.echo.ping" in names
    finally:
        clear_fault_plan()
        client.close()
        srv.stop()


def test_trace_stitches_across_duplicated_frames():
    srv = _echo_server()
    client = ServiceClient(
        srv.host,
        srv.port,
        timeout=5.0,
        retry=RetryPolicy(max_attempts=3, base_delay=0.01, seed=9),
    )
    # duplicate one request frame on the wire: the server answers twice, the
    # second (stale) reply desyncs the NEXT call into a BadFrame redial
    install_fault_plan(
        FaultPlan(seed=6).duplicate("send", f"{srv.port}/ping", count=1)
    )
    try:
        with TRACER.span("dup.root") as root:
            assert client.call("ping", b"a") == b"a"
            assert client.call("ping", b"b") == b"b"
        mine = [r for r in TRACER.spans() if r.trace_id == root.ctx.trace_id]
        names = [r.name for r in mine]
        # every server-side handler execution still belongs to ONE trace
        assert names.count("svc.echo.ping") >= 2
        assert "retry.attempt" in names  # the BadFrame redial is visible
    finally:
        clear_fault_plan()
        client.close()
        srv.stop()


# ---------------------------------------------------------------------------
# device-plane coalescer: span links fan-in/fan-out
# ---------------------------------------------------------------------------


def test_device_plane_merged_batch_links_concurrent_callers():
    from fisco_bcos_tpu.device.plane import DevicePlane

    plane = DevicePlane(window_ms=60.0, high_water=10_000)
    barrier = threading.Barrier(2)
    caller_ctx = {}

    def exec_fn(reqs):
        return [r.n for r in reqs]

    def caller(i):
        with TRACER.span(f"caller.{i}") as sp:
            caller_ctx[i] = sp.ctx
            barrier.wait()
            fut = plane.submit("linktest", None, 1, exec_fn)
            assert fut.result(timeout=30) == 1

    threads = [threading.Thread(target=caller, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert plane.drain(30)

    dispatches = [
        r
        for r in TRACER.spans()
        if r.name == "device.plane.dispatch" and r.attrs.get("op") == "linktest"
    ]
    assert len(dispatches) == 1, "concurrent submits did not coalesce"
    d = dispatches[0]
    assert d.attrs["requests"] == 2
    linked = {s for _t, s in d.links}
    assert {caller_ctx[0].span_id, caller_ctx[1].span_id} <= linked
    # the batch span lives in the FIRST absorbed caller's trace
    assert d.trace_id in {caller_ctx[0].trace_id, caller_ctx[1].trace_id}
    # ...and each caller's trace records its queue wait (submit to
    # dispatch, written from the worker: a derived record), naming the
    # batch span
    for i in range(2):
        wait = next(
            r
            for r in TRACER.spans()
            if r.name == "device.plane.queue"
            and r.trace_id == caller_ctx[i].trace_id
        )
        assert wait.derived
        assert wait.parent_id == caller_ctx[i].span_id
        assert wait.attrs["batch_span"] == f"{d.span_id:016x}"


# ---------------------------------------------------------------------------
# the full lifecycle: Pro split, /trace/tx/<hash> critical path
# ---------------------------------------------------------------------------


def test_tx_lifecycle_trace_over_pro_split():
    """A tx submitted through the split RPC front door yields a stitched
    critical path: submit trace (rpc -> facade -> txpool -> pool-wait) plus
    the block trace (seal -> pbft phases -> execute -> 2PC), with the
    storage-service hops' spans joined over the wire."""
    from fisco_bcos_tpu.codec.abi import ABICodec
    from fisco_bcos_tpu.crypto.suite import ecdsa_suite
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig
    from fisco_bcos_tpu.node import Node, NodeConfig
    from fisco_bcos_tpu.protocol.transaction import TransactionFactory
    from fisco_bcos_tpu.rpc.jsonrpc import JsonRpcImpl
    from fisco_bcos_tpu.service import StorageService
    from fisco_bcos_tpu.service.rpc_service import RpcFacade, RpcService
    from fisco_bcos_tpu.storage import MemoryStorage
    from fisco_bcos_tpu.utils.bytesutil import to_hex

    suite = ecdsa_suite()
    codec = ABICodec(suite.hash)
    storage_svc = StorageService(MemoryStorage())
    storage_svc.start()
    kp = suite.signature_impl.generate_keypair(secret=0x7A1)
    node = Node(
        NodeConfig(
            genesis=GenesisConfig(consensus_nodes=[ConsensusNode(kp.pub)]),
            storage_endpoints=f"{storage_svc.host}:{storage_svc.port}",
        ),
        keypair=kp,
    )
    facade = RpcFacade(JsonRpcImpl(node), tracer=TRACER)
    facade.start()
    rpc = RpcService(facade.host, facade.port)
    rpc.start()
    try:
        fac = TransactionFactory(suite)
        sender = suite.signature_impl.generate_keypair(secret=0x7A2)
        tx = fac.create_signed(
            sender,
            chain_id="chain0",
            group_id="group0",
            block_limit=500,
            nonce="trace-0",
            to=DAG_TRANSFER_ADDRESS,
            input=codec.encode_call("userAdd(string,uint256)", "tr", 1),
        )
        body = json.dumps(
            {
                "jsonrpc": "2.0",
                "id": 1,
                "method": "sendTransaction",
                "params": ["group0", "node0", to_hex(tx.encode())],
            }
        ).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{rpc.port}",
            data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            result = json.loads(resp.read())["result"]
        tx_hash = result["transactionHash"]

        assert node.sealer.seal_and_submit()
        assert node.block_number() == 1

        with urllib.request.urlopen(
            f"http://127.0.0.1:{rpc.port}/trace/tx/{tx_hash}", timeout=30
        ) as resp:
            doc = json.loads(resp.read())
        assert doc["found"] and doc["block"] == 1
        stage_names = {s["name"] for s in doc["stages"]}
        lifecycle = {
            "rpc.forward",
            "rpc.request",
            "txpool.submit",
            "txpool.pool_wait",
            "seal",
            "pbft.pre_prepare",
            "pbft.prepare",
            "pbft.commit",
            "pbft.checkpoint",
            "scheduler.execute_block",
            "scheduler.2pc_prepare",
            "scheduler.2pc_commit",
            "scheduler.commit_block",
        }
        covered = stage_names & lifecycle
        assert len(covered) >= 5, f"only {sorted(covered)} stitched"
        # the storage-service hop joined the block trace over the wire
        assert any(n.startswith("svc.storage.") for n in stage_names)
        # submit-side spans share ONE trace id across rpc process, facade
        # and txpool — the cross-split stitching the tentpole promises
        by_name = {}
        for s in doc["stages"]:
            by_name.setdefault(s["name"], s)
        submit_traces = {
            by_name[n]["trace_id"]
            for n in ("rpc.forward", "rpc.request", "txpool.submit")
            if n in by_name
        }
        assert len(submit_traces) == 1
        # ordered + analyzed: a dominant stage is named
        assert doc["dominant"] in stage_names
        starts = [s["start_ms"] for s in doc["stages"]]
        assert starts == sorted(starts)
        # unknown hash answers 404
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                f"http://127.0.0.1:{rpc.port}/trace/tx/{'ab' * 32}", timeout=30
            )
        assert exc.value.code == 404
    finally:
        rpc.stop()
        facade.stop()
        storage_svc.stop()


# ---------------------------------------------------------------------------
# the tracer as the measurement (ISSUE 24): one clock with the profiler,
# collector pauses, slow-span witnesses, a ring that holds a window
# ---------------------------------------------------------------------------


def test_span_is_on_a_host_line_of_a_profiler_capture(tmp_path):
    """A span opened under ``jax.profiler.start_trace`` is found by name on a
    host line of the ``.xplane.pb``, its duration within 1 ms of the ring's:
    the node's spans and the device's programs share one capture."""
    import glob
    import time

    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with TRACER.span("xplane.probe_outer"):
            with TRACER.span("xplane.probe_inner"):
                time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("xplane.probe_"):
                    seen[ev.name] = (plane.name, ev.duration_ns / 1e9)
    ring = {r.name: r.dur for r in TRACER.spans() if r.name.startswith("xplane.probe_")}
    assert set(seen) == set(ring) == {"xplane.probe_outer", "xplane.probe_inner"}
    for name, (plane_name, dur) in seen.items():
        assert plane_name.startswith("/host:")
        assert abs(dur - ring[name]) < 1e-3
    # outside a capture the annotation is one inert object: the span still records
    with TRACER.span("xplane.probe_after"):
        pass
    assert any(r.name == "xplane.probe_after" for r in TRACER.spans())


def test_forced_collection_leaves_one_record_and_moves_both_counters():
    import gc

    from fisco_bcos_tpu.observability.tracer import install_gc_spans
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    def counters():
        TRACER.flush_drop_metrics()
        got = REGISTRY.counters_matching("fisco_gc_")
        return (got.get('fisco_gc_collections_total{gen="2"}', 0.0),
                got.get('fisco_gc_pause_seconds_total{gen="2"}', 0.0))

    install_gc_spans()
    install_gc_spans()  # idempotent: one hook, one record a collection
    assert sum(cb.__name__ == "_on_gc" for cb in gc.callbacks) == 1
    before = [r for r in TRACER.spans() if r.name == "gc.gen2"]
    n0, s0 = counters()
    gc.collect(2)
    after = [r for r in TRACER.spans() if r.name == "gc.gen2"]
    (new,) = after[len(before):]
    assert new.dur > 0.0 and new.tid == threading.get_ident() and not new.derived
    assert "collected" in new.attrs
    n1, s1 = counters()
    assert n1 == n0 + 1 and s1 == pytest.approx(s0 + new.dur)


def test_slow_span_keeps_its_witnesses():
    """A span over a second hands the flight recorder one ``slow_span``
    event: itself, and what else the ring saw during its interval, by span
    name and thread — derived records left out."""
    import time

    from fisco_bcos_tpu.observability.flight import FLIGHT
    from fisco_bcos_tpu.observability.tracer import SLOW_SPAN_S

    started = threading.Event()

    def witness():
        started.wait(5)
        with TRACER.span("slowtest.witness"):
            time.sleep(0.3)

    t = threading.Thread(target=witness, name="slowtest-thread")
    t.start()
    with TRACER.span("slowtest.stalled"):
        t_inside = time.perf_counter()
        started.set()
        time.sleep(SLOW_SPAN_S + 0.1)
        TRACER.record("slowtest.gap", t_inside, 0.5, derived=True)
    t.join(5)
    events = [e for e in FLIGHT.snapshot()
              if e["category"] == "slow_span" and e["name"] == "slowtest.stalled"]
    (ev,) = events
    assert ev["detail"]["dur_ms"] >= SLOW_SPAN_S * 1e3
    assert ev["detail"]["thread"] == threading.current_thread().name
    by_name = {o["name"]: o for o in ev["detail"]["overlaps"]}
    assert by_name["slowtest.witness"]["thread"] == "slowtest-thread"
    assert 250.0 <= by_name["slowtest.witness"]["ms"] <= 1000.0
    assert "slowtest.gap" not in by_name and "slowtest.stalled" not in by_name
    # a quick span leaves no event
    with TRACER.span("slowtest.quick"):
        pass
    assert not any(e["name"] == "slowtest.quick" for e in FLIGHT.snapshot())


def test_slow_span_reads_what_was_appended_since_it_began():
    """The witnesses of a slow span come from the records appended since it
    began (to the mark before: every ``MARK_EVERY``-th append notes the
    clock), not from a walk of the whole ring: at the 262,144 records of the
    parallel-transfer cell a walk cost 0.1 s, and every block over a second
    paid one (PERF.md §6, PR 32). What they name is what the whole ring
    would have named."""
    import time

    from fisco_bcos_tpu.observability.flight import FLIGHT
    from fisco_bcos_tpu.observability.tracer import MARK_EVERY

    tr = Tracer(capacity=1 << 16)
    t = time.perf_counter()
    for i in range(20 * MARK_EVERY):  # long over before the slow span began
        tr.record("sincetest.old", t - 100.0 + i * 1e-3, 1e-3, width=i)
    lo = time.perf_counter()
    tr.record("sincetest.gap", lo, 0.5, derived=True)
    for i in range(MARK_EVERY + 7):  # inside its interval, across a mark
        tr.record("sincetest.level", lo + i * 1e-4, 1e-4)
    recent = tr._since(lo)
    assert MARK_EVERY + 8 <= len(recent) <= 2 * MARK_EVERY + 8
    assert recent == tr.spans()[-len(recent):]  # oldest first, none skipped
    tr.record("sincetest.stalled", lo, 1.5)
    (ev,) = [e for e in FLIGHT.snapshot()
             if e["category"] == "slow_span" and e["name"] == "sincetest.stalled"]
    (level,) = ev["detail"]["overlaps"]
    assert level["name"] == "sincetest.level" and level["n"] == MARK_EVERY + 7
    # before any mark, or for an interval older than every mark: the ring
    assert len(tr._since(t - 200.0)) == len(tr.spans())
    small = Tracer(capacity=8)
    small.record("a", t, 0.1)
    assert [r.name for r in small._since(t + 5.0)] == ["a"]


def test_ring_holds_a_window_and_a_record_stays_small():
    """The default ring holds a 51 s window of the busiest cell with a margin
    of two (PERF.md §6, PR 24); a record without attributes shares one empty
    mapping and has no ``__dict__``."""
    from fisco_bcos_tpu.observability.tracer import DEFAULT_CAPACITY, SpanRecord

    assert Tracer().capacity == DEFAULT_CAPACITY >= 2 * 26000
    tr = Tracer(capacity=4)
    for i in range(6):
        tr.record("r", float(i), 0.5)
    a, b = tr.spans()[-2:]
    assert a.attrs is b.attrs and not a.attrs and a.attrs.get("x") is None
    assert not hasattr(a, "__dict__") and "derived" in SpanRecord.__slots__
    assert tr.drop_counts()["ring_evict"] == 2
    tr.record("gap", 1.0, 0.5, derived=True, block=3)
    ev = [e for e in tr.export_chrome()["traceEvents"] if e["name"] == "gap"][0]
    assert ev["args"]["derived"] is True and ev["args"]["block"] == 3


def test_slow_span_witness_outlives_the_last_events_ring():
    """A stall early in a long run is still in the dump: ``slow_span`` events
    sit apart from the 512 last events (on the chip one was evicted by forty
    later blocks' 2PC and round events before ``Node.stop`` flushed)."""
    from fisco_bcos_tpu.observability.flight import FLIGHT_CAP, FlightRecorder

    fl = FlightRecorder(enabled=True)
    fl.record("slow_span", "txpool.submit_batch", dur_ms=4236.0)
    for i in range(FLIGHT_CAP + 10):
        fl.record("2pc", "prepare", height=i)
    snap = fl.snapshot()
    assert len(snap) == FLIGHT_CAP + 1
    assert snap[0]["category"] == "slow_span" and snap[0]["detail"]["dur_ms"] == 4236.0
    assert [e["t"] for e in snap] == sorted(e["t"] for e in snap)


# ---------------------------------------------------------------------------
# the stage clock
# ---------------------------------------------------------------------------


def _stage_counters(span):
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    base = f'fisco_span_stage_seconds_total{{span="{span}",stage="'
    return {name[len(base):-2]: v for name, v in REGISTRY.counters_matching(base).items()}


def test_stage_marks_sum_by_name_and_keep_their_offsets():
    """A mark gives the seconds since the previous one (or the start) to its
    name; the record carries the sums and the ordered end offsets, and the
    counter family moves by the same sums when the span closes."""
    import time

    tr = Tracer()
    before = _stage_counters("stage.probe")
    with tr.span("stage.probe") as sp:
        time.sleep(0.002)
        sp.stage("a")
        sp.stage("b", ignored="for the line only")
        time.sleep(0.001)
        sp.stage("a")
        assert set(sp.stages) == {"a", "b"} and not tr.spans(), "a mark writes no record"
        assert _stage_counters("stage.probe") == before, "fed at close, not at a mark"
    (rec,) = tr.spans()
    stages, marks = rec.attrs["stages"], rec.attrs["marks"]
    assert [name for name, _ in marks] == ["a", "b", "a"]
    offsets = [off for _, off in marks]
    assert offsets == sorted(offsets) and 0.003 <= offsets[-1] <= rec.dur
    assert stages["a"] == pytest.approx(offsets[0] + offsets[2] - offsets[1])
    assert stages["b"] == pytest.approx(offsets[1] - offsets[0])
    assert stages["a"] >= 0.003 and sum(stages.values()) == pytest.approx(offsets[-1])
    assert "ignored" not in rec.attrs
    after = _stage_counters("stage.probe")
    assert {k: after[k] - before.get(k, 0.0) for k in after} == pytest.approx(stages)
    # a span that marks nothing closes as it always did
    with tr.span("stage.unmarked"):
        pass
    assert "stages" not in tr.spans()[-1].attrs and "marks" not in tr.spans()[-1].attrs


@pytest.mark.parametrize("n", [16, 17, 400])
def test_a_span_keeps_offsets_up_to_sixteen_marks_and_sums_beyond(n):
    from fisco_bcos_tpu.observability.tracer import MAX_MARKS

    tr = Tracer()
    with tr.span("stage.many") as sp:
        for i in range(n):
            sp.stage(("run", "validate")[i % 2])
    (rec,) = tr.spans()
    assert set(rec.attrs["stages"]) == {"run", "validate"}
    assert sum(rec.attrs["stages"].values()) <= rec.dur
    if n <= MAX_MARKS:
        assert len(rec.attrs["marks"]) == n
    else:
        assert "marks" not in rec.attrs


def test_a_disabled_tracer_marks_nothing_and_keeps_nothing():
    import logging
    import sys

    from fisco_bcos_tpu.observability.tracer import _NOOP

    tr = Tracer(enabled=False)
    before = _stage_counters("stage.off")
    with tr.span("stage.off") as sp:
        assert sp is _NOOP
        sp.stage("warm", txs=1)
        blocks = sys.getallocatedblocks()
        for _ in range(1000):
            sp.stage("a", txs=1)
        assert sys.getallocatedblocks() - blocks <= 2
        assert not sp.stages
    assert not tr.spans() and _stage_counters("stage.off") == before
    # an unsampled trace is the same no-op
    assert Tracer(sample_rate=0.0).span("stage.off") is _NOOP

    # with a stage log attached the line is written all the same: an
    # operator's log does not go quiet with the telemetry
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    log = logging.getLogger("test.stage_off")
    log.setLevel(logging.INFO)
    log.addHandler(handler)
    try:
        with tr.span("stage.off", stage_log=(log, "Probe.7")) as sp:
            sp.stage("fill", txs=3)
            sp.stage("execute", dag=2, serial=1)
            assert not sp.stages
    finally:
        log.removeHandler(handler)
    assert not tr.spans()
    assert lines[0].startswith("[Probe.7.0]|fill|stageMs=") and lines[0].endswith("|txs=3")
    assert lines[1].startswith("[Probe.7.1]|execute|stageMs=")
    assert lines[1].endswith("|dag=2|serial=1")


@pytest.mark.parametrize("telemetry", [True, False])
def test_scheduler_stage_lines_still_match_the_dag_drivers_pattern(telemetry, monkeypatch):
    """``benchmark/drivers/air4_dag.py`` reads ``dag=``/``serial=`` off the
    ``execute`` line of every block: one of ``correct``'s numbers. The marks
    around it are new; the line keeps its shape, with the tracer on or off."""
    import logging

    from benchmark.drivers.air4_dag import _STAGE_RE
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from fisco_bcos_tpu.protocol.transaction import TransactionAttribute
    from test_executor import Env

    monkeypatch.setattr(TRACER, "enabled", telemetry)
    lines = []
    handler = logging.Handler(level=logging.INFO)
    handler.emit = lambda record: lines.append(record.getMessage())
    logging.getLogger("scheduler").addHandler(handler)
    TRACER.clear()
    try:
        env = Env()
        env.run_block([
            env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "alice", 100),
            env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "bob", 50),
        ])
        env.run_block([
            env.tx(DAG_TRANSFER_ADDRESS, "userTransfer(string,string,uint256)",
                   "alice", "bob", 30, attribute=TransactionAttribute.DAG),
            env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "carol", 1),
        ])
    finally:
        logging.getLogger("scheduler").removeHandler(handler)
    matched = [m.groups() for m in map(_STAGE_RE.match, lines) if m]
    assert matched == [("1", "0", "2"), ("2", "1", "1")]
    stages = [ln.split("|")[1] for ln in lines if ln.startswith("[ExecuteBlock.2.")]
    assert stages == ["fillBlock", "execute", "stateRoot", "txsRoot", "receiptsRoot",
                      "roots", "store"]
    assert [ln.split("|")[1] for ln in lines if ln.startswith("[CommitBlock.2.")] == [
        "gate", "prewrite", "prepare", "commit", "booked"]
    recs = {r.name: r for r in TRACER.spans() if r.attrs.get("block") == 2}
    if not telemetry:
        assert not recs
        return
    ex, co = recs["scheduler.execute_block"], recs["scheduler.commit_block"]
    assert [name for name, _ in ex.attrs["marks"]] == stages
    assert list(co.attrs["stages"]) == ["gate", "prewrite", "prepare", "commit", "booked"]
    for rec in (ex, co):
        assert 0.0 < sum(rec.attrs["stages"].values()) <= rec.dur
    # the 2PC's legs keep their child spans: a Pro/Max split parents remote spans on them
    assert recs["scheduler.2pc_prepare"].parent == recs["scheduler.2pc_commit"].parent \
        == "scheduler.commit_block"

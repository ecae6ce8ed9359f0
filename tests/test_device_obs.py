"""Device observatory (ISSUE 13): compile ledger attribution, phase
histograms, memory watermark rings, the recompile-storm health row,
``GET /device`` on both deployment shapes, and the ``FISCO_DEVICE_OBS=0``
noop contract."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import urllib.request

import jax
import pytest
import jax.numpy as jnp

from fisco_bcos_tpu.observability.device import (
    DEVICE_PHASE_BUCKETS_MS,
    LEDGER,
    CompileLedger,
    compile_counts,
    device_doc,
    device_memory_bytes,
    device_span,
    install_jax_hooks,
)
from fisco_bcos_tpu.ops.hash_common import bucket_batch, bucket_ladder
from fisco_bcos_tpu.utils.metrics import REGISTRY

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- ledger attribution (injected hook — no jax involved) ---------------------


def test_ledger_cold_vs_cache_attribution_with_injected_hook():
    """A cache_miss episode books a cold compile, a cache_hit episode a
    persistent-cache load; lowering/retrieval walls ride along and
    backend_compile closes the episode."""
    led = CompileLedger(clock=lambda: 42.0)
    led.push("qc_pairing", (32, "g2"), 32)
    led.note_event("cache_miss")
    led.note_duration("jaxpr_to_mlir_module_duration", 0.002)
    led.note_duration("backend_compile_duration", 3.25)
    frame = led.pop()
    # the span-side accumulator saw compile + lowering (what device_span
    # reports as its ``compile`` phase)
    assert frame["compile_ms"] == 3252.0

    led.push("qc_pairing", (64, "g2"), 64)
    led.note_event("cache_hit")
    led.note_duration("cache_retrieval_time_sec", 0.05)
    led.note_duration("backend_compile_duration", 0.051)
    led.pop()

    rows = led.snapshot()
    assert len(rows) == 2
    by_shape = {r["shape"]: r for r in rows}
    cold = by_shape[repr((32, "g2"))]
    assert cold["cold_compiles"] == 1 and cold["cache_hits"] == 0
    assert cold["last_source"] == "cold"
    assert cold["compile_ms"] == 3250.0 and cold["lowering_ms"] == 2.0
    warm = by_shape[repr((64, "g2"))]
    assert warm["cold_compiles"] == 0 and warm["cache_hits"] == 1
    assert warm["last_source"] == "persistent_cache"
    assert warm["retrieval_ms"] == 50.0
    assert led.program_counts() == {"qc_pairing": 2}
    assert led.cold_compile_count() == 1


def test_ledger_without_cache_verdict_defaults_to_cold():
    """Persistent cache disabled → no verdict events, only the
    backend_compile duration: that IS a cold compile."""
    led = CompileLedger()
    led.push("no_cache_op", 8, 8)
    led.note_duration("backend_compile_duration", 0.1)
    led.pop()
    (row,) = led.snapshot()
    assert row["cold_compiles"] == 1 and row["last_source"] == "cold"


def test_unattributed_compiles_keep_their_episode_across_calls():
    led = CompileLedger()
    led.note_event("cache_hit")  # no frame pushed: the fallback frame
    led.note_duration("backend_compile_duration", 0.01)
    (row,) = led.snapshot()
    assert row["op"] == "(unattributed)"
    assert row["cache_hits"] == 1 and row["cold_compiles"] == 0


def test_compile_counts_agree_with_ledger_under_ragged_flood():
    """ISSUE 13 satellite: with every wrapper passing its BUCKETED shape
    key (device_span now defaults to bucket_batch), the first-shape
    heuristic and the measured ledger count the same programs — and a
    ragged flood stays within the bucket ladder."""
    op = "ragged_flood_test_op"
    fake_xla_cache: set = set()
    sizes = [1, 2, 3, 5, 8, 13, 21, 34, 55, 89, 100, 128, 7, 21, 100]
    for n in sizes:
        with device_span(op, n) as sp:
            assert sp.key == bucket_batch(n)
            if sp.key not in fake_xla_cache:
                # the injected "compiler": one cold compile per new shape,
                # exactly XLA's behavior
                fake_xla_cache.add(sp.key)
                LEDGER.note_event("cache_miss")
                LEDGER.note_duration("backend_compile_duration", 0.001)
    assert compile_counts()[op] == len(fake_xla_cache)
    assert LEDGER.program_counts()[op] == len(fake_xla_cache)
    assert len(fake_xla_cache) <= len(bucket_ladder(max(sizes)))


def test_real_jax_compile_lands_in_ledger():
    """End to end through jax.monitoring: a fresh jit program compiled
    inside a span books a measured episode against that span's op."""
    assert install_jax_hooks()
    op = "real_compile_test_op"
    x = jnp.arange(3)  # outside the span: arange compiles its own program
    with device_span(op, 3, shape_key=3):
        fn = jax.jit(lambda x: x * 3 + 1)
        fn(x).block_until_ready()
    counts = LEDGER.program_counts()
    assert counts.get(op) == 1
    (row,) = [r for r in LEDGER.snapshot() if r["op"] == op]
    # cold on a virgin cache, persistent_cache on a warmed one — either
    # way the episode was measured, not inferred
    assert row["cold_compiles"] + row["cache_hits"] >= 1
    assert row["compile_ms"] > 0.0


# -- phase attribution --------------------------------------------------------


def test_phase_histogram_shape_and_op_phase_labels():
    """Only measured phases: the marked segments under their own names and
    the ledger's compile. Time of the span that no phase covers is in no
    phase — nothing is a remainder."""
    op = "phase_shape_test_op"
    with device_span(op, 16) as sp:
        with sp.phase("marshal"):
            time.sleep(0.002)
        with sp.phase("enqueue"):
            LEDGER.note_event("cache_miss")
            LEDGER.note_duration("backend_compile_duration", 0.004)
        time.sleep(0.003)  # unmarked: must appear under no phase
        with sp.phase("sync"):
            time.sleep(0.001)
    h = REGISTRY.histogram("fisco_device_phase_ms")
    assert h.buckets == tuple(sorted(DEVICE_PHASE_BUCKETS_MS))
    labels = {key for key in h.snapshot() if ("op", op) in key}
    assert labels == {
        (("op", op), ("phase", phase))
        for phase in ("marshal", "enqueue", "sync", "compile")
    }
    totals = LEDGER.phase_totals()[op]
    assert set(totals) == {"marshal", "enqueue", "sync", "compile"}
    assert totals["compile"] == 4.0
    assert totals["marshal"] >= 2.0 and totals["sync"] >= 1.0
    (_op, _t0, dur, _ph) = [d for d in LEDGER.dispatches() if d[0] == op][-1]
    assert totals["marshal"] + totals["enqueue"] + totals["sync"] <= dur * 1e3 - 3.0


def test_phase_child_spans_reach_the_trace_ring():
    """Each phase is a live child span of ``device.<op>`` with timestamps
    read from the clock: inside its parent, in the order it ran."""
    from fisco_bcos_tpu.observability import TRACER
    from fisco_bcos_tpu.observability.device import device_phase

    op = "phase_trace_test_op"
    with device_span(op, 4) as sp:
        with sp.phase("marshal"):
            time.sleep(0.001)
        with device_phase("enqueue"):  # a helper that does not hold the span
            time.sleep(0.001)
    with device_phase("sync", op=op):  # a deferred resolver, span closed
        time.sleep(0.001)
    mine = {s.name: s for s in TRACER.spans() if s.name.startswith(f"device.{op}")}
    assert set(mine) == {f"device.{op}", f"device.{op}.marshal",
                         f"device.{op}.enqueue", f"device.{op}.sync"}
    parent = mine[f"device.{op}"]
    marshal, enqueue, sync = (mine[f"device.{op}.{p}"] for p in ("marshal", "enqueue", "sync"))
    assert marshal.parent_id == enqueue.parent_id == parent.span_id
    assert parent.ts <= marshal.ts < marshal.ts + marshal.dur <= enqueue.ts
    assert enqueue.ts + enqueue.dur <= parent.ts + parent.dur <= sync.ts
    assert not any(s.derived for s in mine.values())
    assert LEDGER.phase_totals()[op]["sync"] >= 1.0


def test_plane_dispatch_emits_queue_phase():
    from fisco_bcos_tpu.device.plane import DevicePlane

    plane = DevicePlane(window_ms=0, autostart=True)
    fut = plane.submit(
        "queue_phase_test_op", [1, 2, 3], 3, lambda reqs: [r.n for r in reqs]
    )
    assert fut.result(timeout=10) == 3
    assert plane.drain(10.0)
    h = REGISTRY.histogram("fisco_device_phase_ms")
    assert (("op", "queue_phase_test_op"), ("phase", "queue")) in set(
        h.snapshot()
    )
    assert "queue" in LEDGER.phase_totals()["queue_phase_test_op"]


# -- memory watermarks --------------------------------------------------------


def test_device_memory_bytes_per_device_and_ring_bounds():
    keep = jnp.arange(1024)  # ensure at least one live buffer
    mem = device_memory_bytes()
    assert mem and all(v >= 0.0 for v in mem.values())
    assert any(str(d) in mem for d in jax.devices())

    from fisco_bcos_tpu.observability.pipeline import PipelineRecorder

    rec = PipelineRecorder(enabled=True, emit_metrics=False, watermark_cap=16)
    rec.add_probe("device_mem", device_memory_bytes)
    for _ in range(40):
        rec.sample_once()
    wm = rec.watermarks()
    series = [k for k in wm if k.startswith("device_mem.")]
    assert series, wm.keys()
    for k in series:
        assert wm[k]["n"] <= 16 and wm[k]["max"] >= keep.nbytes / 8
        assert len(wm[k]["timeline"]) <= 16


# -- recompile-storm detector -------------------------------------------------


def test_recompile_storm_degrades_health_and_recovers():
    from fisco_bcos_tpu.resilience import HEALTH

    clk = {"t": 1000.0}
    led = CompileLedger(
        clock=lambda: clk["t"], storm_window_s=10.0, storm_factor=1.0
    )
    op = "storm_test_op"
    try:
        bound = len(bucket_ladder(8))
        for _ in range(bound + 2):
            led.push(op, 8, 8)
            led.note_event("cache_miss")
            led.note_duration("backend_compile_duration", 0.001)
            led.pop()
        state = led.storm_state()
        assert state["active"] and op in state["ops"]
        row = HEALTH.snapshot()["components"]["device-recompile"]
        assert row["status"] == "degraded"
        assert row["critical"] is False  # degraded-NON-critical by design

        # recovery: the window drains with no further over-bound compiles
        clk["t"] += 100.0
        state = led.storm_state()
        assert not state["active"]
        assert HEALTH.status("device-recompile") == "ok"
    finally:
        HEALTH.ok("device-recompile", "test cleanup")


# -- GET /device: Air and the Pro split --------------------------------------


def test_device_endpoint_over_air_http():
    from fisco_bcos_tpu.rpc.http_server import RpcHttpServer

    with device_span("air_endpoint_test_op", 8):
        LEDGER.note_event("cache_miss")
        LEDGER.note_duration("backend_compile_duration", 0.002)
    server = RpcHttpServer(impl=None, port=0, device=device_doc)
    server.start()
    try:
        url = f"http://127.0.0.1:{server.port}/device"
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("application/json")
            doc = json.loads(resp.read())
    finally:
        server.stop()
    assert doc["enabled"] is True
    ops = {row["op"] for row in doc["ledger"]}
    assert "air_endpoint_test_op" in ops
    row = next(r for r in doc["ledger"] if r["op"] == "air_endpoint_test_op")
    assert row["last_source"] == "cold" and row["cold_compiles"] >= 1
    assert doc["totals"]["cold_compiles"] >= 1
    assert "air_endpoint_test_op" in doc["phase_ms"]
    assert "storm" in doc and "memory" in doc


def test_device_endpoint_over_pro_split():
    """The RPC front door forwards /device to the node core's facade
    (RemoteTelemetry) — the compile ledger lives where the DevicePlane
    lives."""
    from fisco_bcos_tpu.service.rpc_service import RpcFacade, RpcService

    with device_span("split_endpoint_test_op", 4):
        LEDGER.note_event("cache_hit")
        LEDGER.note_duration("backend_compile_duration", 0.001)
    facade = RpcFacade(impl=None)
    facade.start()
    rpc = RpcService(facade.host, facade.port)
    try:
        rpc.start()
        url = f"http://127.0.0.1:{rpc.port}/device"
        with urllib.request.urlopen(url, timeout=10) as resp:
            doc = json.loads(resp.read())
    finally:
        rpc.stop()
        facade.stop()
    assert doc["enabled"] is True
    row = next(
        r for r in doc["ledger"] if r["op"] == "split_endpoint_test_op"
    )
    assert row["last_source"] == "persistent_cache"


def test_remote_telemetry_device_degrades_on_dead_facade():
    from fisco_bcos_tpu.service.rpc_service import RemoteTelemetry

    rt = RemoteTelemetry("127.0.0.1", 1, timeout=0.5)
    try:
        doc = rt.device()
        assert doc["enabled"] is False and "error" in doc
        assert doc["ledger"] == []
    finally:
        rt.close()


# -- FISCO_DEVICE_OBS=0 noop --------------------------------------------------


def test_device_obs_off_is_a_noop(monkeypatch):
    monkeypatch.setenv("FISCO_DEVICE_OBS", "0")
    op = "obs_off_test_op"
    with device_span(op, 8) as sp:
        with sp.phase("transfer"):
            pass
        # jax listeners early-return before touching the ledger
        from fisco_bcos_tpu.observability import device as dev

        dev._on_jax_event("/jax/compilation_cache/cache_misses")
        dev._on_jax_duration("/jax/core/compile/backend_compile_duration", 1.0)
    assert op not in LEDGER.phase_totals()
    assert op not in LEDGER.program_counts()
    h = REGISTRY.histogram("fisco_device_phase_ms")
    assert not any(("op", op) in key for key in h.snapshot())
    doc = device_doc()
    assert doc["enabled"] is False and doc["ledger"] == []
    # the PR 1/PR 3 signal layer is governed by FISCO_TELEMETRY, not this
    # switch: the first-shape counters still tick
    assert op in compile_counts()

    from fisco_bcos_tpu.observability.device import install_observatory

    assert install_observatory() is False


# -- warm-cache manifest (subprocess: run_warm reconfigures jax's cache and
# resets the process LEDGER, so it must never run inside the test process;
# the suite's warm .jax_cache keeps the child fast) ---------------------------


def test_warm_cache_manifest_structure_and_bls_policy(tmp_path):
    out = tmp_path / "manifest.json"
    res = subprocess.run(
        [
            sys.executable, os.path.join(_REPO, "tool", "warm_cache.py"),
            "--ops", "keccak256,bls12_381", "--bucket", "4",
            "--out", str(out),
        ],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=560,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    manifest = json.loads(out.read_text())
    assert manifest["warmed"] == ["keccak256"]
    assert manifest["failed"] == []
    # every inventoried file is accounted for: warmed or skipped-with-reason
    accounted = len(manifest["warmed"]) + len(manifest["skipped"])
    from fisco_bcos_tpu.analysis import jitmap

    files = {p["file"] for p in jitmap.inventory()}
    assert accounted == len(files)
    # CPU backends skip the hour-class BLS compile unless forced — the
    # runtime routes BLS to the host reference there anyway
    reasons = {s["op"]: s["reason"] for s in manifest["skipped"]}
    assert "bls12_381" in reasons and "CPU backend" in reasons["bls12_381"]
    assert "filtered by --ops" in reasons.get("secp256k1", "")
    for key in ("programs", "cold_compiles", "cache_hits", "backend"):
        assert key in manifest


@pytest.mark.slow  # two cold python+jax subprocesses (~1 min on this host)
def test_warm_cache_second_run_has_zero_cold_compiles(tmp_path):
    """The ISSUE 13 acceptance contract, for real: run the tool twice
    against a VIRGIN cache dir in separate processes — the first run cold-
    compiles, the second must be served entirely by the persistent cache
    (--expect-warm turns that into the exit code)."""
    env = dict(
        os.environ,
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
        JAX_PLATFORMS="cpu",
    )
    cmd = [
        sys.executable, os.path.join(_REPO, "tool", "warm_cache.py"),
        "--ops", "keccak256", "--bucket", "4",
    ]
    first = subprocess.run(
        cmd + ["--out", str(tmp_path / "m1.json")],
        env=env, capture_output=True, text=True, timeout=560,
    )
    assert first.returncode == 0, first.stdout + first.stderr
    m1 = json.loads((tmp_path / "m1.json").read_text())
    assert m1["cold_compiles"] >= 1 and m1["cache_hits"] == 0

    second = subprocess.run(
        cmd + ["--out", str(tmp_path / "m2.json"), "--expect-warm"],
        env=env, capture_output=True, text=True, timeout=560,
    )
    assert second.returncode == 0, second.stdout + second.stderr
    m2 = json.loads((tmp_path / "m2.json").read_text())
    assert m2["cold_compiles"] == 0 and m2["cache_hits"] >= 1

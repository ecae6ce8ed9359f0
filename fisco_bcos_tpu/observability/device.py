"""Device observatory: per-op signals, the compile ledger, in-plane time
attribution, device memory watermarks and the recompile-storm detector.

Reference: bcos-crypto/demo/perf_demo.cpp prints per-algorithm signs/verifies
per second; here the equivalent signals are first-class metrics emitted by
the ops host wrappers (ops/secp256k1, ops/sm2, ops/keccak, ops/merkle,
crypto/admission):

- ``fisco_device_batch_size{op=...}``      power-of-two batch histogram
- ``fisco_device_op_latency_ms{op=...}``   wall latency per host call
- ``fisco_device_items_total{op=...}``     items processed (rate = items/sec);
  the merkle programs, which run under whichever hash the suite has, add
  ``hasher`` (``{op="merkle_root",hasher="sm3"}``), as their ``device.<op>``
  record does
- ``fisco_device_op_seconds_total{op=...}`` wall seconds (rate vs items =
  effective verifies/sec without histogram math)
- ``fisco_device_compile_total{op=...}`` / ``fisco_device_cached_call_total``
  first-call-per-bucketed-shape vs repeat-shape calls (the PR 3 heuristic,
  kept for continuity and as the ledger's cross-check).
- ``fisco_device_tiled_items_total{op=...}`` the items of calls whose program
  ran the lanes a device was given in more than one tile (the admission
  body's lane plan, ``ops/limb.lane_plan``; the ``device.<op>`` record says
  ``tiles`` and ``tile_lanes``). A one-tile call adds nothing to it (the
  series stands at what it was, 0 from the op's first call on).
- ``fisco_device_mesh_calls_total{op,devices}`` calls that went out over a
  device mesh and whose answer reached the host (``op`` is the body's own,
  ``admission`` / ``admission_sm``; their lanes are
  ``fisco_device_items_total{op="<op>_sharded"}``). A call that fell to one
  chip or that the host loop answered for adds nothing here.

The ISSUE 13 instruments on top (all behind ``FISCO_DEVICE_OBS``, default
on; ``=0`` turns every one into a shared noop):

- **Compile ledger** (:data:`LEDGER`): per (op, bucketed shape) records of
  MEASURED compiles, fed by JAX's monitoring hooks rather than the
  first-shape heuristic — ``/jax/compilation_cache/cache_misses`` marks a
  true cold compile, ``.../cache_hits`` a persistent-cache load, and
  ``/jax/core/compile/backend_compile_duration`` /
  ``jaxpr_to_mlir_module_duration`` / ``cache_retrieval_time_sec`` carry
  the compile/lowering/retrieval walls. Attribution rides a thread-local
  frame pushed by :class:`device_span` (XLA compiles synchronously on the
  dispatching thread); compiles outside any span land under
  ``(unattributed)``. This is what finally distinguishes the QC
  subsystem's hour-class BLS pairing cold compile from its ~50 ms
  persistent-cache load.
- **Phase attribution**: a wrapper marks each real segment of its
  :class:`device_span` with ``span.phase(name)`` (or :func:`device_phase`
  from a helper that does not hold the span): ``marshal`` (padding, limb
  split), ``enqueue`` (the jitted call returning; holds the jaxpr trace and
  the compile on a shape's first call), ``sync`` (the ``np.asarray`` that
  waits for the device and brings the result over), ``unpack``, and
  ``transfer`` in the stand-alone verify wrappers; a call fanned out over
  the local mesh (``<op>_sharded``) has ``place`` between ``marshal`` and
  ``enqueue``: the operands put on the mesh, one shard a device, and waited
  for. Every phase is measured
  start to end on the clock: a live tracer span ``device.<op>.<phase>`` (so
  it shows on a profiler capture too), ``fisco_device_phase_ms{op,phase}`` on
  :data:`DEVICE_PHASE_BUCKETS_MS` and ``phase_ms`` in ``/device``. Beside
  them stand ``compile`` (measured by the ledger's hooks; it lies inside
  ``enqueue``) and the plane's ``queue`` segment per dispatch (labeled with
  the plane's dispatch op), so ``blocked_on=device_plane`` decomposes one
  level deeper. Nothing is a remainder and no timestamp is made up: time of
  a span that no phase covers is in no phase.
- **Memory watermarks**: :func:`device_memory_bytes` sums live-buffer
  bytes per jax device; :func:`install_observatory` registers it as the
  ``device_mem`` probe in the PR 9 watermark sampler, so per-device live
  bytes ring alongside the queue depths (and render in ``GET /trace`` as
  counter events like every other watermark).
- **Recompile-storm detector**: runtime cold compiles per op inside
  ``FISCO_DEVICE_STORM_WINDOW_S`` (default 60 s) exceeding the
  bucket-ladder bound (x ``FISCO_DEVICE_STORM_FACTOR``, default 2 — shape
  keys may carry a second dim, e.g. the admission message-block dim) flip
  the ``device-recompile`` `/health` row to degraded **non-critical**; it
  recovers when the window drains.

``GET /device`` serves :func:`device_doc` (Air directly, the Pro/Max split
through the facade); ``tool/warm_cache.py`` drives the same ledger to
prove a pre-warmed ``.jax_cache`` serves every program without a cold
compile.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque

from ..ops.hash_common import bucket_batch, bucket_ladder
from ..utils import metrics as _metrics
from ..utils.jaxenv import device_identity
from ..utils.log import get_logger
from .histogram import BATCH_BUCKETS, LATENCY_BUCKETS_MS
from .tracer import TRACER, install_gc_spans

_log = get_logger("device")

# in-plane phase segments: queue waits are sub-ms..100ms, transfers ms-class,
# execute up to block-scale seconds
DEVICE_PHASE_BUCKETS_MS = (
    0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 1000.0, 5000.0,
)
# compile walls: ms-class persistent-cache loads up to hour-class cold
# compiles (the BLS pairing program on XLA-CPU — see ops/bls12_381.py)
DEVICE_COMPILE_BUCKETS_MS = (
    1.0, 10.0, 50.0, 250.0, 1000.0, 5000.0, 30000.0, 120000.0, 600000.0,
    3600000.0,
)

_seen_lock = threading.Lock()
_seen_shapes: dict[str, set] = {}


def device_obs_enabled() -> bool:
    """The observatory master switch, read per call (the bench overhead
    A/B flips it mid-process); independent of FISCO_TELEMETRY, which
    governs the PR 1 signal set."""
    return os.environ.get("FISCO_DEVICE_OBS", "1") != "0"


def _count_shape(op: str, key) -> None:
    with _seen_lock:
        shapes = _seen_shapes.setdefault(op, set())
        fresh = key not in shapes
        if fresh:
            shapes.add(key)
    name = "fisco_device_compile_total" if fresh else "fisco_device_cached_call_total"
    _metrics.REGISTRY.counter_add(
        f'{name}{{op="{op}"}}',
        1.0,
        help="device program calls split by first-shape (compile) vs repeat",
    )


def compile_counts() -> dict[str, int]:
    """Distinct compiled (bucketed) shapes seen per op — the in-process
    view of ``fisco_device_compile_total``. tool/check_device_plane.py reads
    it to assert that a ragged flood stays within the bucket ladder instead of recompiling per batch size. With every
    wrapper passing its true bucketed shape key, this agrees with the
    ledger's measured program count (tests/test_device_obs.py pins it)."""
    with _seen_lock:
        return {op: len(shapes) for op, shapes in _seen_shapes.items()}


# ---------------------------------------------------------------------------
# The compile ledger
# ---------------------------------------------------------------------------

_UNATTRIBUTED = "(unattributed)"

# jax.monitoring key suffixes -> ledger kinds (full keys kept out of the
# hot comparisons; suffix match survives jax renaming the path prefix)
_EVENT_KINDS = {
    "cache_misses": "cache_miss",
    "cache_hits": "cache_hit",
}
_DURATION_KINDS = {
    "backend_compile_duration": "backend_compile",
    "jaxpr_to_mlir_module_duration": "lowering",
    "cache_retrieval_time_sec": "retrieval",
}


class CompileLedger:
    """Measured compile accounting per (op, bucketed shape).

    One compile *episode* per thread: the persistent-cache verdict event
    (``cache_miss``/``cache_hit``) arrives first, the duration events
    close it — ``backend_compile`` is the terminator (it fires on both
    paths; with the persistent cache disabled no verdict arrives and the
    episode is a cold compile by definition). Attribution comes from the
    thread-local frame the enclosing :class:`device_span` pushed.

    Standalone instances (injected clock, for the storm-window tests)
    exist in tests; the process singleton is :data:`LEDGER`.
    """

    def __init__(
        self,
        clock=time.perf_counter,
        storm_window_s: float | None = None,
        storm_factor: float | None = None,
        timeline_cap: int = 2048,
    ):
        from ..utils import env_float

        self.clock = clock
        self.storm_window_s = (
            env_float("FISCO_DEVICE_STORM_WINDOW_S", 60.0)
            if storm_window_s is None
            else float(storm_window_s)
        )
        self.storm_factor = (
            env_float("FISCO_DEVICE_STORM_FACTOR", 2.0)
            if storm_factor is None
            else float(storm_factor)
        )
        self._lock = threading.Lock()
        self._tls = threading.local()
        # (op, shape repr) -> entry dict (mutated under _lock)
        self._entries: dict[tuple[str, str], dict] = {}
        self._phase_ms: dict[str, dict[str, float]] = {}
        self._max_batch: dict[str, int] = {}
        # op -> deque of cold-compile timestamps (the storm window)
        self._cold_times: dict[str, deque] = {}
        self._storm_ops: set[str] = set()
        self._dispatches: deque = deque(maxlen=int(timeline_cap))
        # dispatch adjacency: (prev op, op) -> count, fed at device_span
        # exit and DevicePlane dispatch — the measured half of the
        # progaudit fusion-edge report (which op pairs run back-to-back,
        # i.e. which host round-trips a merged program would delete)
        self._adjacency: dict[tuple[str, str], int] = {}
        self._last_adj_op: str | None = None
        # bookkeeping wall spent in observatory accounting (device_span
        # exit paths add to it) — the measured-overhead artifact input
        self._overhead_s = 0.0
        # op -> {"count", "last_error"}: device programs that raised and
        # were answered by the host loop (the breaker in device/dispatch.py)
        self._failures: dict[str, dict] = {}
        self._mesh: dict[str, dict] = {}

    # -- attribution frames (device_span drives these) -----------------------

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def push(self, op: str, shape_key, batch: int, span=None) -> dict:
        frame = {
            "op": op,
            "shape": shape_key,
            "batch": int(batch),
            "span": span,  # the device_span, for device_phase()
            "compile_ms": 0.0,
            "pending": None,  # cache verdict awaiting its backend_compile
            "pending_lowering_ms": 0.0,
            "pending_retrieval_ms": 0.0,
        }
        self._stack().append(frame)
        with self._lock:
            if batch > self._max_batch.get(op, 0):
                self._max_batch[op] = int(batch)
        return frame

    def pop(self) -> dict | None:
        stack = self._stack()
        return stack.pop() if stack else None

    def _frame(self) -> dict:
        stack = self._stack()
        if stack:
            return stack[-1]
        # compiles outside any span still ledger (warmup paths, tests);
        # the fallback frame persists per thread so a verdict event and
        # its closing backend_compile land in the same episode
        fallback = getattr(self._tls, "fallback", None)
        if fallback is None:
            fallback = self._tls.fallback = {
                "op": _UNATTRIBUTED, "shape": "?", "batch": 0,
                "compile_ms": 0.0, "pending": None,
                "pending_lowering_ms": 0.0, "pending_retrieval_ms": 0.0,
            }
        return fallback

    # -- hook entry points (jax listeners and the injected test hook) --------

    def note_event(self, name: str) -> None:
        """A counter-style jax.monitoring event ('cache_miss'/'cache_hit',
        or the full /jax/... key)."""
        kind = _EVENT_KINDS.get(name.rsplit("/", 1)[-1], name)
        if kind not in ("cache_miss", "cache_hit"):
            return
        self._frame()["pending"] = kind

    def note_duration(self, name: str, secs: float) -> None:
        """A duration-style jax.monitoring event; ``backend_compile``
        closes the episode and writes the ledger entry."""
        kind = _DURATION_KINDS.get(name.rsplit("/", 1)[-1], name)
        frame = self._frame()
        if kind == "lowering":
            frame["pending_lowering_ms"] += secs * 1e3
            return
        if kind == "retrieval":
            frame["pending_retrieval_ms"] += secs * 1e3
            return
        if kind != "backend_compile":
            return
        source = frame.pop("pending", None) or "cache_miss"
        lowering_ms = frame["pending_lowering_ms"]
        retrieval_ms = frame["pending_retrieval_ms"]
        frame["pending_lowering_ms"] = 0.0
        frame["pending_retrieval_ms"] = 0.0
        frame["pending"] = None
        compile_ms = secs * 1e3
        frame["compile_ms"] += compile_ms + lowering_ms
        self._note_compile(
            frame["op"], frame["shape"], source, compile_ms, lowering_ms,
            retrieval_ms,
        )

    def _note_compile(
        self, op, shape, source, compile_ms, lowering_ms, retrieval_ms
    ) -> None:
        now = self.clock()
        cold = source == "cache_miss"
        key = (op, repr(shape))
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                e = self._entries[key] = {
                    "op": op,
                    "shape": repr(shape),
                    "cold_compiles": 0,
                    "cache_hits": 0,
                    "compile_ms": 0.0,
                    "lowering_ms": 0.0,
                    "retrieval_ms": 0.0,
                    "last_source": "",
                    "t_last": 0.0,
                }
            e["cold_compiles" if cold else "cache_hits"] += 1
            e["compile_ms"] += compile_ms
            e["lowering_ms"] += lowering_ms
            e["retrieval_ms"] += retrieval_ms
            e["last_source"] = "cold" if cold else "persistent_cache"
            e["t_last"] = now
            if cold and op != _UNATTRIBUTED:
                # unattributed compiles are exempt from storm accounting:
                # their max-batch is unknown so the ladder bound degenerates
                # to ~2, and a cold boot legitimately compiles several small
                # jnp utility programs outside any span — paging on that
                # would make every fresh node read degraded for a minute
                ring = self._cold_times.setdefault(op, deque(maxlen=256))
                ring.append(now)
            self._refresh_storm_locked(now)
        reg = _metrics.REGISTRY
        if reg.enabled:
            name = (
                "fisco_device_compile_cold_total"
                if cold
                else "fisco_device_compile_cache_hit_total"
            )
            reg.counter_add(
                f'{name}{{op="{op}"}}',
                1.0,
                help="measured XLA compiles split by true cold compile vs "
                "persistent-cache load (jax compilation hooks)",
            )
            reg.observe(
                "fisco_device_compile_ms",
                compile_ms,
                buckets=DEVICE_COMPILE_BUCKETS_MS,
                help="measured compile wall per program (backend compile; "
                "persistent-cache loads appear under source=cache)",
                op=op,
                source="cold" if cold else "cache",
            )

    # -- storm detection ------------------------------------------------------

    def _bound(self, op: str) -> int:
        ladder = len(bucket_ladder(max(self._max_batch.get(op, 1), 1)))
        return max(int(ladder * self.storm_factor), 1)

    def _refresh_storm_locked(self, now: float) -> None:
        horizon = now - self.storm_window_s
        storming: set[str] = set()
        for op, ring in self._cold_times.items():
            while ring and ring[0] < horizon:
                ring.popleft()
            if len(ring) > self._bound(op):
                storming.add(op)
        if storming == self._storm_ops:
            return
        self._storm_ops = storming
        # transitions only — /health rows are state, not a log
        try:
            from ..resilience import HEALTH

            if storming:
                HEALTH.degrade(
                    "device-recompile",
                    "recompile storm: runtime compiles exceed the bucket-"
                    f"ladder bound for {sorted(storming)}",
                    critical=False,  # host fallback + cache keep serving
                )
            else:
                HEALTH.ok("device-recompile", "compile rate within ladder")
        except Exception as e:  # health layer unavailable — ledger works
            from ..utils.log import note_swallowed

            note_swallowed("device.ledger.health", e)

    def refresh_storm(self) -> None:
        """Re-evaluate the storm window against the clock (called by the
        doc renderer and the watermark probe so recovery doesn't wait for
        the next compile)."""
        with self._lock:
            self._refresh_storm_locked(self.clock())

    def storm_state(self) -> dict:
        with self._lock:
            self._refresh_storm_locked(self.clock())
            return {
                "active": bool(self._storm_ops),
                "ops": sorted(self._storm_ops),
                "window_s": self.storm_window_s,
                "bounds": {
                    op: self._bound(op) for op in self._cold_times
                },
            }

    # -- phase + dispatch accounting -----------------------------------------

    def note_phases(self, op: str, phases: dict, t0: float | None = None,
                    dur: float | None = None) -> None:
        with self._lock:
            agg = self._phase_ms.setdefault(op, {})
            for phase, ms in phases.items():
                if ms > 0.0:
                    agg[phase] = agg.get(phase, 0.0) + ms
            if dur is not None:
                self._dispatches.append(
                    (op, t0, dur, {k: round(v, 3) for k, v in phases.items()})
                )

    def note_adjacency(self, op: str) -> None:
        """One dispatch of ``op`` ended: count the (previous op -> op)
        edge. Process-global order, deliberately across threads — the
        plane serializes dispatches anyway, and what the fusion report
        needs is which programs ran back-to-back on the device."""
        with self._lock:
            prev = self._last_adj_op
            if prev is not None:
                key = (prev, op)
                self._adjacency[key] = self._adjacency.get(key, 0) + 1
            self._last_adj_op = op

    def adjacency(self) -> dict[str, int]:
        """Measured dispatch-adjacency counts as ``"a->b"`` edges (the
        fusion report's input; serialized into device artifacts)."""
        with self._lock:
            return {
                f"{a}->{b}": n
                for (a, b), n in sorted(self._adjacency.items())
            }

    def note_failure(self, op: str, exc: BaseException) -> None:
        """A device program for ``op`` raised and the host loop answered in
        its place. The product keeps serving (that is the breaker's job);
        this is where the failure stays visible: ``GET /device`` →
        ``failures``, ``fisco_device_program_failures_total{op}`` and one
        warning line. Recorded regardless of ``FISCO_DEVICE_OBS`` — a
        hidden device failure is what the counter exists to prevent."""
        err = f"{type(exc).__name__}: {str(exc)[:300]}"
        with self._lock:
            rec = self._failures.setdefault(op, {"count": 0, "last_error": ""})
            rec["count"] += 1
            rec["last_error"] = err
        _metrics.REGISTRY.counter_add(
            f'fisco_device_program_failures_total{{op="{op}"}}',
            1.0,
            help="device programs that raised and were answered by the "
            "host loop instead",
        )
        _log.warning("device program %s failed, host loop answered: %s", op, err)

    def failures(self) -> dict[str, dict]:
        with self._lock:
            return {op: dict(rec) for op, rec in self._failures.items()}

    def note_mesh_call(self, op: str, devices: int, lanes_per_device: int) -> None:
        """One call of ``op`` went out over a mesh of ``devices`` and its
        answer is on the host: ``GET /device`` → ``mesh`` and
        ``fisco_device_mesh_calls_total{op,devices}``. One device program
        serves either leg and ``fisco_device_dispatch_path_total`` says
        ``device`` for both; this is what tells a block the mesh verified
        from one that fell to a single chip or to the host loop."""
        with self._lock:
            rec = self._mesh.setdefault(op, {"calls": 0})
            rec.update(devices=devices, lanes_per_device=lanes_per_device)
            rec["calls"] += 1
        _metrics.REGISTRY.counter_add(
            f'fisco_device_mesh_calls_total{{op="{op}",devices="{devices}"}}',
            1.0,
            help="device calls fanned out over a mesh whose answer reached the host",
        )

    def mesh(self) -> dict[str, dict]:
        with self._lock:
            return {op: dict(rec) for op, rec in self._mesh.items()}

    def add_overhead(self, secs: float) -> None:
        with self._lock:
            self._overhead_s += secs

    def overhead_seconds(self) -> float:
        with self._lock:
            return self._overhead_s

    # -- introspection --------------------------------------------------------

    def snapshot(self) -> list[dict]:
        """The ledger rows, most recently compiled first."""
        with self._lock:
            rows = [dict(e) for e in self._entries.values()]
        rows.sort(key=lambda e: -e["t_last"])
        for e in rows:
            for k in ("compile_ms", "lowering_ms", "retrieval_ms", "t_last"):
                e[k] = round(e[k], 3)
        return rows

    def program_counts(self) -> dict[str, int]:
        """Distinct programs (shapes) with at least one measured compile or
        persistent-cache load, per op — the ledger-truth counterpart of
        :func:`compile_counts`."""
        out: dict[str, int] = {}
        with self._lock:
            for op, _shape in self._entries:
                out[op] = out.get(op, 0) + 1
        return out

    def max_batches(self) -> dict[str, int]:
        """Largest batch each op was dispatched with — what tells a native
        dispatch of a QC list from one of a whole block."""
        with self._lock:
            return dict(self._max_batch)

    def cold_compile_count(self) -> int:
        with self._lock:
            return sum(e["cold_compiles"] for e in self._entries.values())

    def phase_totals(self) -> dict[str, dict[str, float]]:
        with self._lock:
            return {
                op: {k: round(v, 3) for k, v in phases.items()}
                for op, phases in self._phase_ms.items()
            }

    def dispatches(self, tail: int = 64) -> list[list]:
        with self._lock:
            recent = list(self._dispatches)[-tail:]
        return [[op, t0, dur, ph] for op, t0, dur, ph in recent]

    def reset(self) -> None:
        """Drop compile/phase state (warm-cache runs, tests)."""
        with self._lock:
            self._entries.clear()
            self._phase_ms.clear()
            self._cold_times.clear()
            self._dispatches.clear()
            self._adjacency.clear()
            self._last_adj_op = None
            self._overhead_s = 0.0
            self._failures.clear()
            self._mesh.clear()


# process-wide ledger (ops wrappers and the jax listeners feed it directly,
# like utils.metrics.REGISTRY / TRACER)
LEDGER = CompileLedger()

_HOOKS_INSTALLED = False
_HOOKS_LOCK = threading.Lock()


def _on_jax_event(name: str, **_kw) -> None:
    if device_obs_enabled() and name.rsplit("/", 1)[-1] in _EVENT_KINDS:
        LEDGER.note_event(name)


def _on_jax_duration(name: str, secs: float, **_kw) -> None:
    if device_obs_enabled() and name.rsplit("/", 1)[-1] in _DURATION_KINDS:
        LEDGER.note_duration(name, secs)


def install_jax_hooks() -> bool:
    """Register the compile/cache listeners with jax.monitoring
    (idempotent; listeners are process-global and cannot be removed, so
    they early-return when the observatory is off)."""
    global _HOOKS_INSTALLED
    with _HOOKS_LOCK:
        if _HOOKS_INSTALLED:
            return True
        try:
            import jax.monitoring as monitoring

            monitoring.register_event_listener(_on_jax_event)
            monitoring.register_event_duration_secs_listener(_on_jax_duration)
        except Exception as e:  # jax absent/old — the ledger still accepts
            from ..utils.log import note_swallowed  # injected events

            note_swallowed("device.ledger.jax_hooks", e)
            return False
        _HOOKS_INSTALLED = True
        return True


# ---------------------------------------------------------------------------
# Device memory watermarks
# ---------------------------------------------------------------------------


def device_memory_bytes() -> dict[str, float]:
    """Live-buffer bytes per jax device (sharded arrays split evenly
    across their device set). Empty on any backend error — a watermark
    probe must never take the sampler down."""
    try:
        import jax

        out: dict[str, float] = {}
        for arr in jax.live_arrays():
            try:
                devs = list(arr.devices())
                nbytes = float(arr.nbytes)
            # analysis: allow(except-hygiene, a deleted/donated buffer mid-
            # iteration only skips its own sample — logging per array would
            # flood at the 25 ms sampler cadence)
            except Exception:
                continue
            if not devs:
                continue
            per = nbytes / len(devs)
            for d in devs:
                label = str(d)
                out[label] = out.get(label, 0.0) + per
        return out
    except Exception:
        return {}


def _memory_probe() -> dict[str, float]:
    # piggyback the sampler tick to age the storm window out (recovery
    # must not wait for the next compile or scrape); the sweep's own cost
    # counts into the measured observatory overhead like every other
    # bookkeeping path
    t_obs = time.perf_counter()
    LEDGER.refresh_storm()
    out = device_memory_bytes()
    LEDGER.add_overhead(time.perf_counter() - t_obs)
    return out


def install_observatory() -> bool:
    """Boot-time wiring: the collector's pause spans (they follow the
    tracer's switch, not this module's), then jax compile hooks + the
    ``device_mem`` watermark probe (PR 9 sampler). Idempotent; the device
    part refuses under ``FISCO_DEVICE_OBS=0``."""
    install_gc_spans()
    if not device_obs_enabled():
        return False
    installed = install_jax_hooks()
    try:
        from .pipeline import PIPELINE

        if PIPELINE.enabled:
            PIPELINE.add_probe("device_mem", _memory_probe)
    except Exception as e:
        from ..utils.log import note_swallowed

        note_swallowed("device.observatory.probe", e)
    return installed


# ---------------------------------------------------------------------------
# The GET /device document
# ---------------------------------------------------------------------------


def device_doc(tail: int = 64) -> dict:
    """Everything the device observatory knows, one JSON: which device
    the process is on (platform / device_kind / count, as JAX reports it),
    device programs that failed over to the host loop, the mesh each
    fanned-out op last went out over (devices, lanes a device, calls), the
    compile ledger (cold vs persistent-cache attribution), per-op phase totals,
    the first-shape heuristic counters for cross-checking, storm state,
    live-buffer bytes + their watermark rings, and the plane's scheduler
    stats. Served at ``GET /device`` on Air and through the facade on the
    Pro/Max split."""
    enabled = device_obs_enabled()
    doc: dict = {
        "enabled": enabled,
        "ts": time.time(),
        "epoch": TRACER.epoch,
        "device": device_identity(),
        "failures": LEDGER.failures(),
        "mesh": LEDGER.mesh(),
        "max_batch": LEDGER.max_batches() if enabled else {},
        "ledger": LEDGER.snapshot() if enabled else [],
        "phase_ms": LEDGER.phase_totals() if enabled else {},
        "compile_counts": compile_counts(),
        "storm": LEDGER.storm_state() if enabled else {"active": False},
        "overhead_s": round(LEDGER.overhead_seconds(), 6),
        "dispatches": LEDGER.dispatches(tail) if enabled else [],
        "adjacency": LEDGER.adjacency() if enabled else {},
    }
    rows = doc["ledger"]
    doc["totals"] = {
        "cold_compiles": sum(e["cold_compiles"] for e in rows),
        "cache_hits": sum(e["cache_hits"] for e in rows),
        "compile_ms": round(sum(e["compile_ms"] for e in rows), 3),
    }
    if enabled:
        doc["memory"] = {"live_bytes": device_memory_bytes()}
        try:
            from .pipeline import PIPELINE

            doc["memory"]["watermarks"] = {
                k: v
                for k, v in PIPELINE.watermarks(tail).items()
                if k.startswith("device_mem.")
            }
        except Exception:
            doc["memory"]["watermarks"] = {}
    else:
        doc["memory"] = {}
    try:
        from ..device.plane import get_plane

        plane = get_plane()
        doc["plane"] = dict(plane.stats(), lanes=plane.lane_depths())
    except Exception:
        doc["plane"] = {}
    return doc


# ---------------------------------------------------------------------------
# device_span
# ---------------------------------------------------------------------------


class _NoopPhase:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_PHASE = _NoopPhase()


class _Phase:
    """One measured segment: a live tracer span ``device.<op>.<name>`` (real
    timestamps, on a profiler capture too) whose duration feeds the phase
    accounting — through its :class:`device_span` at that span's exit, or at
    once where the segment runs after the span has closed (the resolver of a
    dispatch-now/sync-later wrapper, on whichever thread resolves it)."""

    __slots__ = ("_op", "_name", "_span", "_trace", "_t0")

    def __init__(self, op: str, name: str, span: "device_span | None" = None):
        self._op = op
        self._name = name
        self._span = span

    def __enter__(self):
        self._trace = TRACER.span(f"device.{self._op}.{self._name}")
        self._trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        self._trace.__exit__(*exc)
        if self._span is not None:
            self._span._phases.append((self._name, self._t0, dur))
        else:
            observe_phase(self._op, self._name, dur * 1e3)
            LEDGER.note_phases(self._op, {self._name: dur * 1e3})
        return False


def observe_phase(op: str, phase: str, ms: float) -> None:
    """One ``fisco_device_phase_ms{op,phase}`` observation (the plane's
    ``queue`` segment comes through here too)."""
    reg = _metrics.REGISTRY
    if reg.enabled and ms > 0.0:
        reg.observe(
            "fisco_device_phase_ms",
            ms,
            buckets=DEVICE_PHASE_BUCKETS_MS,
            help="device-plane time attribution per op: measured marshal / "
            "enqueue / sync / unpack / transfer segments, the ledger's "
            "compile, the plane's queue",
            op=op,
            phase=phase,
        )


def device_phase(name: str, op: str | None = None):
    """``span.phase(name)`` of the innermost :class:`device_span` open on
    this thread, for helpers that run inside a wrapper's span without
    holding it (``keccak256_batch_async`` under the hash-plane executor).
    Outside any span: a segment of its own under ``op`` where one is given
    (a deferred resolver's sync), else a no-op."""
    stack = LEDGER._stack()
    span = stack[-1]["span"] if stack else None
    if span is not None:
        return span.phase(name)
    if op is not None and device_obs_enabled():
        return _Phase(op, name)
    return _NOOP_PHASE


def note_dispatch_path(op: str, path: str) -> None:
    """Labeled counter of which way a call actually went: the leg of a batch
    (native host loop vs device program: the observable form of
    `device/dispatch.use_native_batch`, so a mistuned FISCO_DEVICE_MIN_BATCH
    shows up in /metrics instead of as a silent latency cliff), the form of a
    merkle tree (`device_span.path`)."""
    _metrics.REGISTRY.counter_add(
        f'fisco_device_dispatch_path_total{{op="{op}",path="{path}"}}',
        1.0,
        help="batch dispatches split by chosen leg (native host vs device)",
    )


class device_span:
    """Time one host-level device-batch call and emit the full signal set.

    ``shape_key`` must be the bucketed shape the op compiles for (the
    batch bucket, plus any other shape-determining dims). It defaults to
    ``bucket_batch(batch)`` — the raw-batch fallback over-counted compiles
    whenever a caller skipped bucketing (ISSUE 13 satellite); wrappers
    with extra shape dims still pass their full key explicitly.

    ``with span.phase("marshal"): ...`` marks a real segment (module
    docstring); compile time comes from the ledger's measured episodes
    during the span. The plane records its queue segment itself at
    dispatch, under its own op label.
    """

    __slots__ = (
        "op", "batch", "key", "hasher", "_t0", "_span", "_phases", "_frame", "_obs_s",
        "_tiled",
    )

    def __init__(self, op: str, batch: int, shape_key=None, hasher: str | None = None):
        self.op = op
        self.batch = int(batch)
        # an op that runs under more than one hash (the merkle programs) says
        # which: on the trace record and on the items series
        self.hasher = hasher
        self.key = (
            shape_key if shape_key is not None
            else bucket_batch(max(int(batch), 1))
        )
        self._phases: list[tuple[str, float, float]] = []
        self._frame: dict | None = None
        self._obs_s = 0.0  # this span's own observatory bookkeeping wall
        self._tiled: bool | None = None  # plan(): more than one tile; None = no plan

    def phase(self, name: str):
        """Mark a real segment (``marshal``/``place``/``enqueue``/``sync``/
        ``unpack``/``transfer``) of this span's wall."""
        if self._frame is None:
            return _NOOP_PHASE
        return _Phase(self.op, name, self)

    def set(self, **kv) -> None:
        """Attributes on the ``device.<op>`` trace record (inside the
        ``with`` only)."""
        self._span.set(**kv)

    def plan(self, tiles: int, tile_lanes: int) -> None:
        """How the call's program runs the lanes a device is given (the
        admission body's lane plan, ``ops/limb.lane_plan``): ``tiles`` and
        ``tile_lanes`` on the trace record, and where there is more than one
        tile the call's items count into
        ``fisco_device_tiled_items_total{op}`` as well."""
        self._span.set(tiles=tiles, tile_lanes=tile_lanes)
        self._tiled = tiles > 1

    def path(self, name: str) -> None:
        """Which way the call went where an op has more than one (a merkle
        tree as one ``fused`` program or in ``levels``): ``path`` on the trace
        record and one count on ``fisco_device_dispatch_path_total{op,path}``."""
        self._span.set(path=name)
        note_dispatch_path(self.op, name)

    def __enter__(self):
        reg = _metrics.REGISTRY
        if reg.enabled:
            reg.observe(
                "fisco_device_batch_size",
                self.batch,
                buckets=BATCH_BUCKETS,
                help="device-crypto batch sizes per op (power-of-two buckets)",
                op=self.op,
            )
            _count_shape(self.op, self.key)
        if device_obs_enabled():
            t_obs = time.perf_counter()
            self._frame = LEDGER.push(self.op, self.key, self.batch, span=self)
            self._obs_s += time.perf_counter() - t_obs
        else:
            self._frame = None
        attrs = {"hasher": self.hasher} if self.hasher else {}
        self._span = TRACER.span(f"device.{self.op}", batch=self.batch, **attrs)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self._t0
        self._span.__exit__(exc_type, exc, tb)
        if self._frame is not None:
            t_obs = time.perf_counter()
            LEDGER.pop()
            self._obs_s += time.perf_counter() - t_obs
        reg = _metrics.REGISTRY
        if reg.enabled and exc_type is None:
            reg.observe(
                "fisco_device_op_latency_ms",
                dt * 1e3,
                buckets=LATENCY_BUCKETS_MS,
                help="device-crypto host-call wall latency per op",
                op=self.op,
            )
            by_hasher = f',hasher="{self.hasher}"' if self.hasher else ""
            reg.counter_add(
                f'fisco_device_items_total{{op="{self.op}"{by_hasher}}}',
                float(self.batch),
                help="items processed by device-crypto ops",
            )
            if self._tiled is not None:  # a one-tile call adds 0: the series is there
                reg.counter_add(
                    f'fisco_device_tiled_items_total{{op="{self.op}"}}',
                    float(self.batch) if self._tiled else 0.0,
                    help="items of device admission programs that ran their "
                    "lanes in more than one tile",
                )
            reg.counter_add(
                f'fisco_device_op_seconds_total{{op="{self.op}"}}',
                dt,
                help="wall seconds spent in device-crypto host calls",
            )
        if self._frame is not None:
            if exc_type is None:
                t_obs = time.perf_counter()
                self._emit_phases(dt)
                LEDGER.note_adjacency(self.op)
                self._obs_s += time.perf_counter() - t_obs
            LEDGER.add_overhead(self._obs_s)
        return False

    def _emit_phases(self, dt: float) -> None:
        """The measured segments under their own names, plus the ledger's
        measured compile (part of ``enqueue`` where that is marked). The
        trace already holds each phase as a live span."""
        phases: dict[str, float] = {}
        for name, _t, d in self._phases:
            phases[name] = phases.get(name, 0.0) + d * 1e3
        compile_ms = self._frame["compile_ms"]
        if compile_ms > 0.0:
            phases["compile"] = compile_ms
        for phase, ms in phases.items():
            observe_phase(self.op, phase, ms)
        LEDGER.note_phases(self.op, phases, t0=self._t0, dur=dt)

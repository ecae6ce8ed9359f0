"""Span tracing with real trace semantics: 128-bit traces, explicit span
ids, cross-process propagation, span links.

Reference: the reference's per-stage BlockTrace logs (DMCExecute.0..6 in
bcos-scheduler BlockExecutive.cpp:849-1010) answer "where did this block's
wall time go?" by grepping; here the same stages are first-class spans in a
bounded in-memory ring, exported as Chrome trace-event JSON (the format
Perfetto / chrome://tracing load directly) from ``GET /trace`` next to
``/metrics``.

Trace model (ISSUE 4 tentpole):

- Every span belongs to a **trace** (128-bit ``trace_id``) and has its own
  64-bit ``span_id`` plus an explicit ``parent_id`` — name-based parentage
  is kept only as a display label (the same stage running concurrently is
  no longer ambiguous).
- The current :class:`TraceContext` propagates **in-process** through a
  ``contextvars.ContextVar``, so nesting works across module boundaries and
  survives explicit hand-offs into worker threads (``Tracer.attach``).
- **Across processes** the context rides a W3C-traceparent-style field
  (``00-<trace_id:32x>-<span_id:16x>-<flags:2x>``) injected into service-RPC
  frames by :mod:`fisco_bcos_tpu.service.rpc`.
- A span may carry **links** — (trace_id, span_id) references to spans in
  *other* traces. The device-plane coalescer uses them: one merged-batch
  span links every caller span it absorbed, so N transactions visibly
  converge into one TPU program and fan back out.
- **Head-based sampling**: ``FISCO_TRACE_SAMPLE`` (0.0–1.0, default 1.0)
  decides per root span; the decision propagates with the context (children
  and remote callees honor it). Skipped spans and ring evictions are
  counted (``fisco_trace_spans_dropped_total{reason}``) so a truncated
  trace is distinguishable from a fast one.

Completed spans from other timelines (e.g. PBFT phase gaps measured between
message arrivals) are added retroactively via :meth:`Tracer.record`, with
an explicit ``parent_ctx`` placing them in the right trace. A record whose
interval is a gap between two events, and not the lifetime of work on the
thread that wrote it, is passed ``derived=True``: it overlaps the real spans
of whatever ran meanwhile, so a reader that sums time leaves it out.

One clock with the device trace: a live span enters a
``jax.profiler.TraceAnnotation`` of its own name (``TraceMe`` is inactive
outside a profiler session: one object per span), so any profiler capture
of the process shows the node's spans on the host lines beside the device's
programs. A process that never imported JAX skips it.

A span that ran longer than :data:`SLOW_SPAN_S` hands the flight recorder one
``slow_span`` event naming what else the ring saw during its interval.

The stage clock: ``span.stage(name)`` gives the seconds since the span's
previous mark (or its start) to ``name``. A mark is one clock reading and one
dict update and writes no record; the span's one record closes with the sums
(``stages``) and, while there are at most :data:`MAX_MARKS` of them, the
marks' ordered end offsets (``marks``), and each sum is added to
``fisco_span_stage_seconds_total{span,stage}``. A span opened with
``stage_log=(logger, badge)`` also writes the reference's BlockTrace line
(``[badge.k]|stage|stageMs=..|totalMs=..|k=v``, DMCExecute.0..6 in
bcos-scheduler BlockExecutive.cpp:849-1010) at every mark, tracer on or off.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import logging
import os
import random
import sys
import threading
import time
import types
from collections import deque
from dataclasses import dataclass

# the current trace context: None outside any span. Survives everything
# that runs on the same thread/context; worker threads start empty and are
# re-attached explicitly (Tracer.attach) at each hand-off seam.
_CURRENT: contextvars.ContextVar["TraceContext | None"] = contextvars.ContextVar(
    "fisco_trace_ctx", default=None
)

# extra Chrome-trace event sources merged into export_chrome: callables
# () -> list[event dicts]. observability/pipeline.py registers its
# backpressure-watermark counter ("C") events here so queue levels render
# on the same Perfetto timeline as the stage spans.
CHROME_EVENT_SOURCES: list = []


@dataclass(frozen=True)
class TraceContext:
    """The propagatable identity of one span: which trace, which span.

    ``name``/``depth`` are local display conveniences (never on the wire);
    ``sampled`` carries the head-based sampling decision downstream."""

    trace_id: int  # 128-bit
    span_id: int  # 64-bit
    sampled: bool = True
    name: str = ""
    depth: int = 0

    def traceparent(self) -> str:
        """W3C trace-context ``traceparent`` form (version 00)."""
        flags = 1 if self.sampled else 0
        return f"00-{self.trace_id:032x}-{self.span_id:016x}-{flags:02x}"

    @classmethod
    def from_traceparent(cls, header: str) -> "TraceContext | None":
        """Parse a traceparent field; None on anything malformed (a bad
        header must never break the RPC that carried it)."""
        try:
            _ver, tid, sid, flags = header.strip().split("-")
            if len(tid) != 32 or len(sid) != 16:
                return None
            return cls(
                int(tid, 16), int(sid, 16), bool(int(flags, 16) & 1), "remote", 0
            )
        except (ValueError, AttributeError):
            return None


def current_context() -> TraceContext | None:
    """The ambient trace context of this thread/context, if any."""
    return _CURRENT.get()


def trace_hex(ctx: TraceContext | None) -> str | None:
    """The 32-hex trace id of a context (None-safe) — the exemplar label
    every histogram call site shares. Unsampled contexts yield None too:
    their spans were all dropped, so an exemplar pointing at them would
    send an operator to a trace that does not exist."""
    return f"{ctx.trace_id:032x}" if ctx is not None and ctx.sampled else None


# a span that ran longer than this leaves a ``slow_span`` flight event,
# which names at most this many (span name, thread) witnesses
SLOW_SPAN_S = 1.0
SLOW_SPAN_WITNESSES = 32
# every this-many-th append notes the clock beside the count of appends, so a
# slow span's witnesses are read from what was appended since it began and
# not from the whole ring (0.1 s a span at 262,144 records: PERF.md §6, PR 32)
MARK_EVERY = 1024
# a span keeps the ordered offsets of its stage marks up to this many; a span
# that marks more often (a DAG block: a mark a level and a check) keeps sums
MAX_MARKS = 16
# the ring holds a benchmark window with a margin of two (PERF.md §6, PR 24):
# the busiest cell writes ~26k records in 51 s; ~0.35 KB a record
DEFAULT_CAPACITY = 65536

# the attrs of a record that has none: one shared read-only mapping, so a
# ring of tens of thousands of records does not hold as many empty dicts
_NO_ATTRS: types.MappingProxyType = types.MappingProxyType({})


@dataclass(slots=True)
class SpanRecord:
    name: str
    ts: float  # perf_counter at span start (seconds)
    dur: float  # seconds
    tid: int
    depth: int = 0
    parent: str | None = None  # display label only; parent_id is the truth
    attrs: "dict | types.MappingProxyType" = _NO_ATTRS
    trace_id: int = 0
    span_id: int = 0
    parent_id: int | None = None
    links: tuple = ()  # ((trace_id, span_id), ...)
    derived: bool = False  # a gap between events, not work on this thread


_ANNOTATION = None  # jax.profiler.TraceAnnotation once JAX is in the process


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, or None while this process has not
    imported JAX (the tracer never drags it in) or has one without it."""
    global _ANNOTATION
    # analysis: allow(atomicity, racing first spans import the same class
    # object — there is no second instance to hold)
    if _ANNOTATION is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation

            _ANNOTATION = TraceAnnotation
        except Exception:  # a partial or stripped JAX: spans work without
            _ANNOTATION = False
    return _ANNOTATION or None


class _NoopSpan:
    """Shared do-nothing span for a disabled/unsampled tracer.

    Contract: ``attrs`` hands out a fresh throwaway dict per access, so two
    item assignments (``sp.attrs["k"] = v; sp.attrs["j"] = w``) land in two
    different dicts and BOTH are discarded — callers must use
    :meth:`set` (``sp.set(k=v, j=w)``), which real spans implement by
    updating their one attrs dict and this class implements as a no-op."""

    __slots__ = ()

    ctx = None

    @property
    def attrs(self) -> dict:
        return {}

    def set(self, **kv) -> "_NoopSpan":
        return self

    def stage(self, name: str, **kv) -> None:
        pass

    @property
    def stages(self) -> types.MappingProxyType:
        return _NO_ATTRS

    def link(self, ctxs) -> None:
        pass

    def discard(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _StageLine:
    """The BlockTrace line of a span's marks: ``[badge.k]|stage|stageMs=..|
    totalMs=..`` and the mark's own pairs last (the benchmark's DAG driver
    parses the ``execute`` line's ``dag=``/``serial=`` tail)."""

    __slots__ = ("_logger", "_badge", "_n")

    def __init__(self, logger, badge: str):
        self._logger = logger
        self._badge = badge
        self._n = 0

    def emit(self, name: str, stage_s: float, total_s: float, kv: dict) -> None:
        if not self._logger.isEnabledFor(logging.INFO):
            return
        from ..utils.log import kv_line

        self._logger.info(
            kv_line(
                f"{self._badge}.{self._n}",
                name,
                stageMs=round(stage_s * 1e3, 3),
                totalMs=round(total_s * 1e3, 3),
                **kv,
            )
        )
        self._n += 1


class _LineSpan(_NoopSpan):
    """What a disabled or unsampled tracer hands out for a span opened with a
    stage log: no record, no sums, and the line all the same (an operator's
    log does not go quiet with the telemetry)."""

    __slots__ = ("_line", "_t0", "_mark")

    def __init__(self, line: _StageLine):
        self._line = line

    def __enter__(self):
        self._t0 = self._mark = time.perf_counter()
        return self

    def stage(self, name: str, **kv) -> None:
        now = time.perf_counter()
        self._line.emit(name, now - self._mark, now - self._t0, kv)
        self._mark = now


class _Span:
    __slots__ = (
        "_tracer", "name", "attrs", "_t0", "depth", "parent",
        "ctx", "_parent_ctx", "links", "_token", "_annotation", "_discard",
        "_mark", "_stages", "_marks", "_line",
    )

    def __init__(
        self,
        tracer: "Tracer",
        name: str,
        attrs: dict,
        parent_ctx: TraceContext | None,
        links: tuple = (),
        line: _StageLine | None = None,
    ):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._parent_ctx = parent_ctx
        self.links = tuple(links)
        self._discard = False
        self._stages: dict[str, float] | None = None
        self._marks: list | None = None
        self._line = line

    def link(self, ctxs) -> None:
        """Add links found while the span was open (the seal span learns
        which admission spans it absorbed only once it has fetched)."""
        self.links += tuple(ctxs)

    def discard(self) -> None:
        """Leave no record: the work this span was opened for did not
        happen (a sealer tick that found nothing to seal)."""
        self._discard = True

    def set(self, **kv) -> "_Span":
        """Attach attributes (the only supported mutation API — item
        assignment on ``attrs`` silently vanishes on a disabled tracer)."""
        self.attrs.update(kv)
        return self

    def stage(self, name: str, **kv) -> None:
        """The seconds since the previous mark (or the span's start) go to
        ``name``. ``kv`` are for the stage line of a span that has one."""
        now = time.perf_counter()
        dur = now - self._mark
        self._mark = now
        sums = self._stages
        if sums is None:
            sums = self._stages = {}
            self._marks = []
        sums[name] = sums.get(name, 0.0) + dur
        marks = self._marks
        if marks is not None:
            if len(marks) < MAX_MARKS:
                marks.append((name, now - self._t0))
            else:
                self._marks = None
        if self._line is not None:
            self._line.emit(name, dur, now - self._t0, kv)

    @property
    def stages(self) -> "dict | types.MappingProxyType":
        """Seconds by stage name so far (the whole span's once it closed)."""
        return self._stages if self._stages is not None else _NO_ATTRS

    def __enter__(self):
        tr = self._tracer
        pctx = self._parent_ctx
        if pctx is None:
            pctx = _CURRENT.get()
        if pctx is None:
            self.ctx = tr._new_root(self.name)
        else:
            self.ctx = TraceContext(
                pctx.trace_id,
                tr._new_span_id(),
                pctx.sampled,
                self.name,
                pctx.depth + 1,
            )
        self._parent_ctx = pctx
        self.parent = pctx.name or None if pctx is not None else None
        self.depth = self.ctx.depth
        self._token = _CURRENT.set(self.ctx)
        annotation = _trace_annotation()
        if annotation is not None:
            self._annotation = annotation(self.name)
            self._annotation.__enter__()
        else:
            self._annotation = None
        self._t0 = self._mark = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        _CURRENT.reset(self._token)
        if self._discard:
            return False
        if self._stages is not None:
            self.attrs["stages"] = self._stages
            if self._marks is not None:
                self.attrs["marks"] = tuple(self._marks)
            _count_stages(self.name, self._stages)
        self._tracer.record(
            self.name,
            t0=self._t0,
            dur=dur,
            depth=self.depth,
            parent=self.parent,
            ctx=self.ctx,
            parent_ctx=self._parent_ctx,
            links=self.links,
            **self.attrs,
        )
        return False


def _count_stages(span: str, stages: dict) -> None:
    from ..utils.metrics import REGISTRY

    for stage, seconds in stages.items():
        REGISTRY.counter_add(
            f'fisco_span_stage_seconds_total{{span="{span}",stage="{stage}"}}',
            seconds,
            help="seconds of recorded spans by stage mark (span.stage): the "
            "same sums each span's record carries as `stages`",
        )


def _unrecorded(stage_log) -> _NoopSpan:
    return _NOOP if stage_log is None else _LineSpan(_StageLine(*stage_log))


class Tracer:
    """Bounded ring of completed spans; thread-safe, cheap when disabled."""

    def __init__(
        self,
        capacity: int = DEFAULT_CAPACITY,
        enabled: bool = True,
        sample_rate: float | None = None,
    ):
        self.capacity = int(capacity)
        self.enabled = enabled
        if sample_rate is None:
            try:
                sample_rate = float(os.environ.get("FISCO_TRACE_SAMPLE", "1") or "1")
            except ValueError:
                sample_rate = 1.0
        self.sample_rate = sample_rate
        self._buf: deque[SpanRecord] = deque()
        self._appended = 0
        self._marks: deque[tuple[float, int]] = deque(maxlen=1024)
        self._lock = threading.Lock()
        self._tls = threading.local()
        # drop accounting: plain ints (GIL-cheap on the hot path), mirrored
        # into the metrics registry lazily (flush_drop_metrics)
        self._dropped = {"sampled": 0, "ring_evict": 0}
        # tid -> thread name, noted at a thread's first record: a witness
        # has often exited by the time a slow span names it
        self._thread_names: dict[int, str] = {}
        # the process tracer alone takes in the collector's pauses (below)
        self._takes_gc = False
        self._dropped_pushed = {"sampled": 0, "ring_evict": 0}
        # wall-clock anchor: rec.ts (perf_counter) + epoch ≈ time.time() at
        # span start — what cross-process stitching orders by
        self.epoch = time.time() - time.perf_counter()

    # -- ids / sampling -------------------------------------------------------

    def _rng(self) -> random.Random:
        rng = getattr(self._tls, "rng", None)
        if rng is None:
            rng = self._tls.rng = random.Random(
                int.from_bytes(os.urandom(16), "big")
                ^ threading.get_ident()
            )
        return rng

    def _new_span_id(self) -> int:
        return self._rng().getrandbits(64) or 1

    def _sample(self) -> bool:
        rate = self.sample_rate
        if rate >= 1.0:
            return True
        if rate <= 0.0:
            return False
        return self._rng().random() < rate

    def _new_root(self, name: str = "") -> TraceContext:
        rng = self._rng()
        return TraceContext(
            rng.getrandbits(128) or 1, rng.getrandbits(64) or 1,
            self._sample(), name, 0,
        )

    def new_root_context(self, name: str = "") -> TraceContext | None:
        """An explicit root context (e.g. one per in-flight block) that
        retroactive records and attach() can hang spans onto."""
        if not self.enabled:
            return None
        return self._new_root(name)

    def current_context(self) -> TraceContext | None:
        return _CURRENT.get()

    def current_traceparent(self) -> str:
        """The injectable wire form of the ambient context ('' when absent
        or the tracer is disabled) — what service-RPC clients send."""
        if not self.enabled:
            return ""
        ctx = _CURRENT.get()
        return ctx.traceparent() if ctx is not None else ""

    def attach(self, ctx: TraceContext | None):
        """Context manager installing ``ctx`` as the ambient context — the
        hand-off seam for worker threads and extracted remote contexts.
        ``attach(None)`` is a no-op (callers never need to branch)."""
        return _Attach(ctx)

    def _drop(self, reason: str) -> None:
        # benign-race int bump: a lost increment under contention is noise,
        # a lock here would tax every sampled-out span
        # analysis: allow(guarded-state, deliberate lock-free fast path)
        self._dropped[reason] = self._dropped.get(reason, 0) + 1

    def drop_counts(self) -> dict:
        return dict(self._dropped)

    def flush_drop_metrics(self) -> None:
        """Push drop-count deltas into the process registry as
        ``fisco_trace_spans_dropped_total{reason=...}`` counters. Called on
        every export so a scrape after /trace sees current numbers; cheap
        enough to call ad hoc."""
        try:
            from ..utils.metrics import REGISTRY
        except Exception:  # pragma: no cover - partial-import window
            return
        # the flush path is cold (scrape/export time): take the ring lock so
        # two concurrent scrapes can't both claim the same delta
        deltas = []
        with self._lock:
            for reason, n in self._dropped.items():
                delta = n - self._dropped_pushed.get(reason, 0)
                if delta > 0:
                    self._dropped_pushed[reason] = n
                    deltas.append((reason, delta))
            gc_deltas = _claim_gc_deltas() if self._takes_gc else ()
        for reason, delta in deltas:
            REGISTRY.counter_add(
                f'fisco_trace_spans_dropped_total{{reason="{reason}"}}',
                float(delta),
                help="spans not recorded, by reason (sampled = head "
                "sampling, ring_evict = ring overwrote them)",
            )
        for gen, n, secs in gc_deltas:
            REGISTRY.counter_add(
                f'fisco_gc_collections_total{{gen="{gen}"}}',
                float(n),
                help="cyclic-collector passes by generation",
            )
            REGISTRY.counter_add(
                f'fisco_gc_pause_seconds_total{{gen="{gen}"}}',
                secs,
                help="seconds the cyclic collector held the process, by generation",
            )

    # -- span creation --------------------------------------------------------

    def span(
        self,
        name: str,
        parent: TraceContext | None = None,
        links: tuple = (),
        stage_log: tuple | None = None,
        **attrs,
    ):
        """Context manager timing a region; yields the span so callers can
        add attrs (``sp.set(txs=n)``) and stage marks (``sp.stage(name)``)
        before it closes. ``parent`` overrides the ambient context
        (cross-thread/remote parents); ``links`` are (trace_id, span_id)
        pairs or TraceContexts from OTHER traces; ``stage_log`` is a
        ``(logger, badge)`` whose BlockTrace line every mark writes."""
        if not self.enabled:
            return _unrecorded(stage_log)
        pctx = parent if parent is not None else _CURRENT.get()
        if pctx is not None and not pctx.sampled:
            # unsampled trace: skip the span but keep the ambient decision
            self._drop("sampled")
            return _unrecorded(stage_log)
        if pctx is None and self.sample_rate <= 0.0:
            # fast path: nothing upstream and sampling is off — no root
            self._drop("sampled")
            return _unrecorded(stage_log)
        if links:
            links = tuple(
                (l.trace_id, l.span_id) if isinstance(l, TraceContext) else tuple(l)
                for l in links
            )
        line = _StageLine(*stage_log) if stage_log is not None else None
        return _Span(self, name, attrs, parent, links, line)

    def record(
        self,
        name: str,
        t0: float,
        dur: float,
        depth: int = 0,
        parent: str | None = None,
        ctx: TraceContext | None = None,
        parent_ctx: TraceContext | None = None,
        links: tuple = (),
        derived: bool = False,
        **attrs,
    ) -> TraceContext | None:
        """Append a COMPLETED span with explicit timing — the retroactive
        path for phase gaps measured between events (PBFT quorum waits,
        pool-wait: those pass ``derived=True``) and for intervals measured
        start to end by the caller (collector pauses, a DAG batch).
        ``parent_ctx`` places it in a trace; without one the
        ambient context applies, else it becomes a sampled-on-its-own root.
        Returns the recorded span's context (None when dropped)."""
        if not self.enabled:
            return None
        if ctx is None:
            base = parent_ctx if parent_ctx is not None else _CURRENT.get()
            if base is not None:
                if not base.sampled:
                    self._drop("sampled")
                    return None
                ctx = TraceContext(
                    base.trace_id, self._new_span_id(), True, name, base.depth + 1
                )
                parent_ctx = base
            else:
                ctx = self._new_root(name)
                if not ctx.sampled:
                    self._drop("sampled")
                    return None
        elif not ctx.sampled:
            self._drop("sampled")
            return None
        if parent is None and parent_ctx is not None:
            parent = parent_ctx.name or None
        tid = getattr(self._tls, "tid", None)
        if tid is None:  # this thread's first record (idents are reused)
            tid = self._tls.tid = threading.get_ident()
            self._thread_names[tid] = threading.current_thread().name
        if not depth:
            depth = ctx.depth
        if links:
            links = tuple(
                (l.trace_id, l.span_id) if isinstance(l, TraceContext) else tuple(l)
                for l in links
            )
        rec = SpanRecord(
            name,
            t0,
            max(dur, 0.0),
            tid,
            depth,
            parent,
            attrs or _NO_ATTRS,
            trace_id=ctx.trace_id,
            span_id=ctx.span_id,
            parent_id=parent_ctx.span_id if parent_ctx is not None else None,
            links=links,
            derived=derived,
        )
        if _GC_PENDING and self._takes_gc:
            self._drain_gc()
        self._append(rec)
        return ctx

    def _append(self, rec: SpanRecord) -> None:
        if self.capacity <= 0:
            # FISCO_TRACE_CAPACITY=0: keep nothing, count everything
            self._drop("ring_evict")
            return
        with self._lock:
            if len(self._buf) >= self.capacity:
                self._buf.popleft()
                self._dropped["ring_evict"] += 1
            self._buf.append(rec)
            self._appended += 1
            if not self._appended % MARK_EVERY:
                self._marks.append((time.perf_counter(), self._appended))
        if rec.dur >= SLOW_SPAN_S and not rec.derived:
            self._note_slow(rec)

    def _drain_gc(self) -> None:
        """Move the collector pauses noted by :func:`_on_gc` into the ring.
        Runs at a safe point (a record or a read), never in the collector's
        callback: a collection can start between two bytecodes of a thread
        that holds the ring's lock."""
        while True:
            try:
                gen, t0, dur, tid, collected = _GC_PENDING.popleft()
            except IndexError:
                return
            name = f"gc.gen{gen}"
            ctx = self._new_root(name)
            if not ctx.sampled:
                self._drop("sampled")
                continue
            self._append(
                SpanRecord(
                    name, t0, dur, tid, attrs={"collected": collected},
                    trace_id=ctx.trace_id, span_id=ctx.span_id,
                )
            )

    def _note_slow(self, rec: SpanRecord) -> None:
        """One ``slow_span`` flight event: the slow span, and for its
        interval the time by span name and thread of every other measured
        record in the ring that overlaps it (collector pauses included) —
        what a watchdog dump would have shown, taken after the fact from
        data the process already holds. Nested spans each count their own
        whole overlap (inclusive time, as a profile gives it)."""
        try:
            from .flight import FLIGHT

            if not FLIGHT.enabled:
                return
            lo, hi = rec.ts, rec.ts + rec.dur
            by: dict[tuple[str, int], list] = {}
            for r in self._since(lo):
                if r is rec or r.derived:
                    continue
                overlap = min(hi, r.ts + r.dur) - max(lo, r.ts)
                if overlap > 0.0:
                    slot = by.setdefault((r.name, r.tid), [0.0, 0])
                    slot[0] += overlap
                    slot[1] += 1
            threads = self._thread_names
            top = sorted(by.items(), key=lambda kv: -kv[1][0])[:SLOW_SPAN_WITNESSES]
            FLIGHT.record(
                "slow_span",
                rec.name,
                dur_ms=round(rec.dur * 1e3, 3),
                t0=rec.ts,
                thread=threads.get(rec.tid, str(rec.tid)),
                overlaps=[
                    {
                        "name": name,
                        "thread": threads.get(tid, str(tid)),
                        "ms": round(secs * 1e3, 3),
                        "n": n,
                    }
                    for (name, tid), (secs, n) in top
                ],
            )
        except Exception as e:  # the witness must never break the span
            from ..utils.log import note_swallowed

            note_swallowed("tracer.slow_span", e)

    def spans(self) -> list[SpanRecord]:
        if _GC_PENDING and self._takes_gc:
            self._drain_gc()
        with self._lock:
            return list(self._buf)

    def _since(self, t: float) -> list[SpanRecord]:
        """Every record that can overlap an interval beginning at clock ``t``,
        oldest first: those appended since the last mark at or before ``t``
        (a record is appended no earlier than it ended, so one appended
        before ``t`` ended before it). The whole ring where no mark is that
        old."""
        if _GC_PENDING and self._takes_gc:
            self._drain_gc()
        with self._lock:
            n = self._appended
            for at, count in reversed(self._marks):
                if at <= t:
                    n -= count
                    break
            recent = list(itertools.islice(reversed(self._buf), n))
        recent.reverse()
        return recent

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()

    # -- export ---------------------------------------------------------------

    def export_chrome(self) -> dict:
        """Chrome trace-event JSON object (Perfetto/chrome://tracing load it
        directly): complete ("X") events, timestamps in microseconds. Real
        ids ride in args (``trace_id``/``span_id``/``parent_id`` hex);
        ``parent`` stays as the display label only."""
        self.flush_drop_metrics()
        pid = os.getpid()
        events = []
        for rec in self.spans():
            args = {k: v for k, v in rec.attrs.items()}
            if rec.derived:
                args["derived"] = True
            if rec.parent is not None:
                args["parent"] = rec.parent
            args["trace_id"] = f"{rec.trace_id:032x}"
            args["span_id"] = f"{rec.span_id:016x}"
            if rec.parent_id is not None:
                args["parent_id"] = f"{rec.parent_id:016x}"
            if rec.links:
                args["links"] = [
                    f"{t:032x}:{s:016x}" for t, s in rec.links
                ]
            events.append(
                {
                    "ph": "X",
                    "name": rec.name,
                    "cat": "fisco",
                    "pid": pid,
                    "tid": rec.tid,
                    "ts": round(rec.ts * 1e6, 3),
                    "dur": round(rec.dur * 1e6, 3),
                    "args": args,
                }
            )
        if self is globals().get("TRACER"):
            # merge registered extra events (pipeline watermark counters)
            # into the PROCESS trace only — local test tracers stay pure
            for source in list(CHROME_EVENT_SOURCES):
                try:
                    events.extend(source())
                except Exception as e:
                    from ..utils.log import note_swallowed

                    note_swallowed("tracer.chrome_source", e)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            # perf_counter -> wall clock anchor for cross-process stitching
            "epoch": self.epoch,
        }

    def export_json(self) -> str:
        return json.dumps(self.export_chrome(), default=str)


# -- collector pauses ---------------------------------------------------------
#
# The cyclic collector stops every thread of the process while it runs. Its
# pauses are measured start to end by a ``gc.callbacks`` hook and land in the
# ring as ``gc.gen<n>`` records on the thread that triggered the collection.
# The hook itself takes no lock (a collection may start inside a thread that
# holds the ring's or the registry's): it reads the clock, bumps plain
# tallies and appends to a deque; the ring and ``/metrics`` pick both up at
# their next safe point (``Tracer._drain_gc``, ``flush_drop_metrics``).

GC_GEN0_RECORD_S = 1e-3  # a generation-0 pass leaves a record only over this
_GC_PENDING: deque = deque()  # (gen, t0, dur, tid, collected)
_GC_TOTALS = {gen: [0, 0.0] for gen in range(3)}  # gen -> [collections, seconds]
_GC_PUSHED = {gen: [0, 0.0] for gen in range(3)}
_gc_t0 = 0.0


def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0
    if not TRACER.enabled:
        return
    if phase == "start":
        _gc_t0 = time.perf_counter()
        return
    dur = time.perf_counter() - _gc_t0
    gen = info["generation"]
    tally = _GC_TOTALS[gen]
    tally[0] += 1
    tally[1] += dur
    if gen or dur > GC_GEN0_RECORD_S:
        _GC_PENDING.append(
            (gen, _gc_t0, dur, threading.get_ident(), info["collected"])
        )


def _claim_gc_deltas() -> list[tuple[int, int, float]]:
    """(gen, collections, seconds) not yet pushed to the registry; the caller
    holds the ring's lock, so two scrapes never claim the same delta."""
    out = []
    for gen, (n, secs) in _GC_TOTALS.items():
        pushed = _GC_PUSHED[gen]
        if n > pushed[0]:
            out.append((gen, n - pushed[0], secs - pushed[1]))
            pushed[0], pushed[1] = n, secs
    return out


def install_gc_spans() -> None:
    """Hook the collector (idempotent; process-wide). Follows the tracer's
    switch: with telemetry off the hook returns at once."""
    import gc

    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


class _Attach:
    __slots__ = ("_ctx", "_token")

    def __init__(self, ctx: TraceContext | None):
        self._ctx = ctx

    def __enter__(self):
        self._token = _CURRENT.set(self._ctx) if self._ctx is not None else None
        return self._ctx

    def __exit__(self, *exc):
        if self._token is not None:
            _CURRENT.reset(self._token)
        return False


# process-wide default tracer (modules import and use directly, like
# utils.metrics.REGISTRY); FISCO_TELEMETRY=0 starts it disabled
TRACER = Tracer(
    capacity=int(os.environ.get("FISCO_TRACE_CAPACITY", str(DEFAULT_CAPACITY))),
    enabled=os.environ.get("FISCO_TELEMETRY", "1") != "0",
)
TRACER._takes_gc = True

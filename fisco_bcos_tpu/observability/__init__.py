"""Observability subsystem: labeled histograms, span tracing, device-op
instrumentation.

Three pieces (ISSUE 1 tentpole):

- :mod:`.histogram` — the Prometheus histogram model (``_bucket``/``_sum``/
  ``_count`` exposition) with the reference's 0/50/100/150 ms mtail latency
  buckets and power-of-two batch buckets. ``utils.metrics.MetricsRegistry``
  composes it; modules observe through the process ``REGISTRY``.
- :mod:`.tracer` — thread-safe span tracing (``TRACER.span(...)`` context
  managers, nesting, bounded ring) exported as Chrome trace-event JSON at
  ``GET /trace``. Since ISSUE 4: real trace semantics — 128-bit trace ids,
  explicit span/parent ids, contextvars + traceparent propagation across
  the service split, span links, head sampling.
- :mod:`.critical_path` — the per-transaction lifecycle stitcher behind
  ``GET /trace/tx/<hash>`` (tx→trace and block→trace indexes, cross-process
  span collection, ordered stage breakdown with the dominant stage named).
- :mod:`.device` — the device observatory (ISSUE 13 on top of the ISSUE 1
  signal bundle): per-op batch/latency/items metrics, the measured compile
  ledger (cold compile vs persistent-cache load via JAX's monitoring
  hooks), measured phase attribution (marshal/enqueue/sync/unpack, the
  ledger's compile, the plane's queue), device memory
  watermarks and the recompile-storm detector, served at ``GET /device``.
  Imported directly as ``from ..observability.device import device_span``
  by the ops wrappers (kept out of this namespace so importing the package
  never drags in the metrics registry mid-import);
  ``FISCO_DEVICE_OBS=0`` noops the observatory layer independently.
- :mod:`.pipeline` — the pipeline observatory (ISSUE 9): per-stage
  busy/idle/blocked occupancy with blocked-on attribution plus the
  backpressure watermark sampler behind ``GET /pipeline``. Imported
  directly (``from ..observability.pipeline import PIPELINE``) by the
  pipeline workers; ``FISCO_PIPELINE_OBS=0`` noops it independently of
  the metrics/tracer switch.
- :mod:`.profiler` — the in-process sampling wall-clock profiler behind
  ``GET /profile?seconds=N`` (collapsed stacks + self time).

``set_enabled(False)`` (or env ``FISCO_TELEMETRY=0`` before import) turns
the whole layer into no-ops — the switch the bench overhead A/B uses.
"""

from __future__ import annotations

from .histogram import (  # noqa: F401
    BATCH_BUCKETS,
    LATENCY_BUCKETS_MS,
    Histogram,
)
from .tracer import (  # noqa: F401
    TRACER,
    SpanRecord,
    TraceContext,
    Tracer,
    current_context,
)


def set_enabled(flag: bool) -> None:
    """Enable/disable the whole telemetry layer (registry + tracer)."""
    from ..utils.metrics import REGISTRY

    REGISTRY.enabled = bool(flag)
    TRACER.enabled = bool(flag)


def telemetry_enabled() -> bool:
    from ..utils.metrics import REGISTRY

    return REGISTRY.enabled or TRACER.enabled

"""In-process sampling wall-clock profiler — ``GET /profile?seconds=N``.

A sampler loop over ``sys._current_frames()`` (default 100 Hz) folds every
thread's stack into collapsed-stack (flamegraph) lines with per-function
self-time aggregation. Unlike the span tracer — which only sees the seams
the code chose to instrument — the profiler answers *where is the
interpreter actually spending its time* during a flood, with no per-call
instrumentation cost: the only overhead is the sample itself, measured
into ``fisco_profiler_sample_ms`` so the duty cycle (sample cost x rate)
is a first-class artifact number the <5% flood-TPS acceptance checks.

Stacks are package-filtered by default: frames outside ``fisco_bcos_tpu``
(and the repo's bench/tool entrypoints) are dropped, and threads parked in
pure-stdlib waits (queue.get, cv.wait) fold to nothing — the report counts
them in ``samples`` but they add no stack, so the flamegraph shows work,
not idle parking.

Determinism seam: :meth:`SamplingProfiler.take_sample` accepts an injected
``{tid: frame}`` snapshot (anything with ``f_code``/``f_lineno``/``f_back``
duck-typing works), so tests drive the fold with synthetic stacks and get
bit-stable collapsed output.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Callable

DEFAULT_HZ = 100.0
PROFILE_SECONDS_MAX = 30.0
# one sample = one _current_frames sweep + fold: tens of µs .. a few ms on
# very thread-heavy processes
PROFILER_SAMPLE_BUCKETS_MS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 25.0)

_PKG_MARKER = f"fisco_bcos_tpu{os.sep}"
# the repo's tools count as "ours" under the package filter
_TOOL_DIR = os.sep + "tool" + os.sep


def _keep_frame(filename: str) -> bool:
    return _PKG_MARKER in filename or _TOOL_DIR in filename


def _frame_label(frame) -> str:
    code = frame.f_code
    fn = code.co_filename
    if _PKG_MARKER in fn:
        mod = fn.split(_PKG_MARKER, 1)[1].replace(os.sep, "/")
        mod = "fisco_bcos_tpu/" + mod
    else:
        mod = os.path.basename(fn)
    return f"{mod}:{code.co_name}"


class SamplingProfiler:
    """Fold-as-you-go sampling profiler. ``start()``/``stop()`` run the
    sampler on its own thread (the bench flood mode); ``run_for(seconds)``
    samples inline on the caller's thread (the HTTP endpoint mode — the
    handler thread IS the sampler, no thread churn per request)."""

    def __init__(
        self,
        hz: float = DEFAULT_HZ,
        package_only: bool = True,
        frames_fn: Callable[[], dict] | None = None,
        clock: Callable[[], float] = time.perf_counter,
        max_stack: int = 64,
        emit_metrics: bool = True,
    ):
        self.hz = max(float(hz), 0.001)
        self.interval = 1.0 / self.hz
        self.package_only = package_only
        self.frames_fn = frames_fn or sys._current_frames
        self.clock = clock
        self.max_stack = int(max_stack)
        self.emit_metrics = emit_metrics
        self._lock = threading.Lock()
        self._counts: dict[tuple[str, ...], int] = {}
        self._self: dict[str, int] = {}
        self.samples = 0  # sweeps taken
        self.stack_samples = 0  # per-thread stacks that survived the filter
        self.sample_cost_s = 0.0  # wall time spent inside take_sample
        self.duration_s = 0.0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._t_started: float | None = None

    # -- folding -------------------------------------------------------------

    def take_sample(self, frames: dict | None = None) -> None:
        """One sweep: fold every thread's current stack. ``frames`` is the
        injection seam for deterministic tests; live sampling excludes the
        sampler's own thread and the calling thread's sweep frame."""
        t0 = self.clock()
        injected = frames is not None
        if frames is None:
            frames = self.frames_fn()
        me = threading.get_ident()
        folded: list[tuple[str, ...]] = []
        for tid, top in frames.items():
            if not injected and tid == me:
                continue
            stack: list[str] = []
            f = top
            while f is not None and len(stack) < self.max_stack:
                fn = getattr(f.f_code, "co_filename", "")
                if not self.package_only or _keep_frame(fn):
                    stack.append(_frame_label(f))
                f = f.f_back
            if stack:
                stack.reverse()  # root-first, the collapsed-stack order
                folded.append(tuple(stack))
        with self._lock:
            self.samples += 1
            for key in folded:
                self.stack_samples += 1
                self._counts[key] = self._counts.get(key, 0) + 1
                leaf = key[-1]
                self._self[leaf] = self._self.get(leaf, 0) + 1
        dt = self.clock() - t0
        self.sample_cost_s += dt
        if self.emit_metrics and not injected:
            try:
                from ..utils.metrics import REGISTRY

                REGISTRY.observe(
                    "fisco_profiler_sample_ms",
                    dt * 1e3,
                    buckets=PROFILER_SAMPLE_BUCKETS_MS,
                    help="one profiler sweep (frames snapshot + stack fold) "
                    "— duty cycle = rate(sum)/1000 = profiler overhead",
                )
            except Exception as e:  # partial-import window — sampling works
                from ..utils.log import note_swallowed

                note_swallowed("profiler.sample_metric", e)

    # -- drivers -------------------------------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._t_started = self.clock()

        def run() -> None:
            nxt = self.clock() + self.interval
            while not self._stop.wait(max(nxt - self.clock(), 0.0)):
                nxt += self.interval
                self.take_sample()

        self._thread = threading.Thread(
            target=run, name="pipeline-profiler", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        if self._t_started is not None:
            self.duration_s += self.clock() - self._t_started
            self._t_started = None

    def run_for(self, seconds: float) -> None:
        """Sample inline on the calling thread for ``seconds``."""
        t0 = self.clock()
        deadline = t0 + seconds
        nxt = t0
        while True:
            now = self.clock()
            if now >= deadline:
                break
            if now >= nxt:
                self.take_sample()
                nxt = max(nxt + self.interval, now)
            else:
                time.sleep(min(nxt - now, deadline - now))
        self.duration_s += self.clock() - t0

    # -- reporting -----------------------------------------------------------

    def collapsed(self) -> dict[str, int]:
        """{"root;child;leaf": samples} — flamegraph.pl input, one line per
        entry (``collapsed_text``)."""
        with self._lock:
            counts = dict(self._counts)
        # string formatting happens OUTSIDE the lock the sampler contends
        return {";".join(k): v for k, v in sorted(counts.items())}

    def collapsed_text(self) -> str:
        return "\n".join(f"{k} {v}" for k, v in self.collapsed().items())

    def self_times(self) -> dict[str, int]:
        with self._lock:
            return dict(self._self)

    def report(self, top: int = 40) -> dict:
        with self._lock:
            samples = self.samples
            stack_samples = self.stack_samples
            selfs_all = dict(self._self)
            counts = dict(self._counts)
        selfs = sorted(selfs_all.items(), key=lambda kv: (-kv[1], kv[0]))[:top]
        collapsed = {";".join(k): v for k, v in sorted(counts.items())}
        duration = self.duration_s
        if self._t_started is not None:
            duration += self.clock() - self._t_started
        return {
            "hz": self.hz,
            "samples": samples,
            "stack_samples": stack_samples,
            "duration_s": round(duration, 4),
            "package_only": self.package_only,
            "overhead": {
                "sample_cost_s": round(self.sample_cost_s, 6),
                # fraction of wall time the sampler occupied — on a
                # 1-core host this IS the upper bound on the TPS tax
                "duty_cycle": round(
                    self.sample_cost_s / duration, 6
                ) if duration > 0 else 0.0,
            },
            "self_top": [
                {
                    "func": func,
                    "samples": n,
                    "pct": round(100.0 * n / stack_samples, 2)
                    if stack_samples
                    else 0.0,
                }
                for func, n in selfs
            ],
            "collapsed": collapsed,
        }


# one on-demand profile at a time: concurrent /profile requests would
# multiply the sampling tax for no extra information
_PROFILE_LOCK = threading.Lock()


def profile(seconds: float = 2.0, hz: float = DEFAULT_HZ) -> dict:
    """The ``GET /profile?seconds=N`` implementation: sample this process
    for ``seconds`` (clamped to :data:`PROFILE_SECONDS_MAX`) on the calling
    thread and return the report. Single-flight: a second concurrent
    request gets ``{"error": "profiler busy"}`` instead of doubling the
    overhead."""
    try:
        seconds = float(seconds)
    except (TypeError, ValueError):
        seconds = 2.0
    seconds = min(max(seconds, 0.05), PROFILE_SECONDS_MAX)
    if not _PROFILE_LOCK.acquire(blocking=False):
        return {"error": "profiler busy", "seconds": seconds}
    try:
        p = SamplingProfiler(hz=hz)
        p.run_for(seconds)
        return p.report()
    finally:
        _PROFILE_LOCK.release()

"""The node's own spans, read for the window: how the host time of a block
divides among the program's layers.

The program's tracer (``fisco_bcos_tpu/observability/tracer.py``) keeps its
completed spans in a ring, on the clock of ``benchmark/spans.py`` and of the
window's edges (``time.perf_counter``). This module is the one place that
reads them; ``benchmark/layers/<quantity>.py`` hold one reader per quantity.

The rule, the same as ``trace_reduce``'s for idle gaps:

- take the ring's records with ``t0 <= ts < t1`` and leave out the ``derived``
  ones (gaps between two events, such as a PBFT quorum wait: they overlap the
  real spans of whatever ran meanwhile);
- on the driving thread (the one that wrote the ``bench.*`` rows and that
  calls the readers), every instant inside a ``bench.submit_batch`` or
  ``bench.seal_and_submit`` interval goes to the innermost program span that
  covers it, or to none. A span that crosses an edge of the interval is
  clipped to it. Collector pauses (``gc.gen*``) are left out of this rule:
  a pause is charged to the span it interrupted, and reported on its own;
- a span's instants count for the group its name maps to in the interval
  (``GROUPS``); a name that maps to nothing inherits the group of the span
  around it, so ``executor.execute`` counts with ``scheduler.execute_block``.
  Waiting for the device is a group of its own wherever it happens:
  ``device.plane.wait`` and every ``device.<op>.sync``;
- per-block quantities are sums over the window divided by its blocks;
- if the ring's oldest record ended after ``t0`` the ring did not hold the
  window, and every reader returns None: a missing number, never a partial
  one. On a program whose records carry no ``derived`` mark, or that writes
  none of a quantity's spans, the readers return None too.
"""

from __future__ import annotations

import re
import sys
import threading
from bisect import bisect_left

SUBMIT, SEAL = "bench.submit_batch", "bench.seal_and_submit"
ADMISSION, PBFT, EXECUTE, COMMIT, WAIT, OTHER, NONE = (
    "admission_host", "seal_pbft", "seal_execute", "seal_commit",
    "device_wait", "other_span", "unattributed",
)
# name -> group, by the bench interval it lies in; a key ending in "." is a
# prefix, any other is the whole name
GROUPS = {
    SUBMIT: {"txpool.": ADMISSION, "admission": ADMISSION, "txsync.": ADMISSION},
    SEAL: {
        "scheduler.execute_block": EXECUTE, "scheduler.commit_block": COMMIT,
        "seal": PBFT, "pbft.": PBFT, "qc.": PBFT, "txpool.": PBFT,
    },
}
_WAIT_RE = re.compile(r"^device\.(plane\.wait|[^.]+\.sync)$")
_MARSHAL_RE = re.compile(r"^device\.admission[^.]*\.(marshal|unpack)$")
_SYNC_RE = re.compile(r"^device\.admission[^.]*\.sync$")


def group_of(name: str, kind: str) -> str | None:
    if _WAIT_RE.match(name):
        return WAIT
    table = GROUPS[kind]
    if name in table:
        return table[name]
    for key, group in table.items():
        if key.endswith(".") and name.startswith(key):
            return group
    return None


def window_records(records, t0: float, t1: float):
    """The measured records that started inside [t0, t1), or None when the
    ring is younger than ``t0`` (its oldest record, the first in ring order,
    ended after ``t0``: something of the window may have been evicted)."""
    records = list(records)
    if not records or records[0].ts + records[0].dur > t0:
        return None
    if not hasattr(records[0], "derived"):
        return None  # a program that does not mark its gaps cannot be summed
    return [r for r in records if t0 <= r.ts < t1 and not r.derived]


def innermost(spans, a: float, b: float, kind: str) -> dict[str, float]:
    """Seconds of [a, b) by group: each instant to the innermost of ``spans``
    (name, start, end; one thread) that covers it; ``unattributed`` where none
    does, ``other_span`` under spans that map to no group."""
    clipped = sorted(
        ((max(s, a), min(e, b), name) for name, s, e in spans if s < b and e > a),
        key=lambda x: (x[0], -x[1]),
    )
    out: dict[str, float] = {}
    stack: list[tuple[float, str]] = []  # (end, group) of the open spans
    cursor = a

    def give(upto: float, group: str) -> None:
        nonlocal cursor
        if upto > cursor:
            out[group] = out.get(group, 0.0) + upto - cursor
            cursor = upto

    for start, end, name in clipped:
        while stack and stack[-1][0] <= start:
            give(*stack.pop())
        give(start, stack[-1][1] if stack else NONE)
        if stack:  # a child never outlives its parent
            end = min(end, stack[-1][0])
        group = group_of(name, kind) or (stack[-1][1] if stack else OTHER)
        stack.append((end, group))
    while stack:
        give(*stack.pop())
    give(b, NONE)
    return out


def _union(intervals) -> float:
    total, hi = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > hi:
            total += e - max(s, hi)
            hi = e
    return total


def split(records, rows, t0: float, t1: float, blocks: int, tid: int):
    """-> per-block milliseconds of every quantity, or None (ring too young,
    or no block). ``rows`` are ``Spans.rows``; ``tid`` the driving thread."""
    recs = window_records(records, t0, t1)
    if recs is None or blocks <= 0:
        return None
    sums: dict[str, float] = {}

    def add(key: str, secs: float) -> None:
        sums[key] = sums.get(key, 0.0) + secs

    mine = sorted(
        ((r.ts, r.ts + r.dur, r.name) for r in recs
         if r.tid == tid and not r.name.startswith("gc.")),
    )
    starts = [m[0] for m in mine]
    reach, hi = [], float("-inf")  # the latest end among mine[:i + 1]
    for m in mine:
        hi = max(hi, m[1])
        reach.append(hi)
    for kind, a, b in rows:
        if kind not in GROUPS or not t0 <= a < t1:
            continue
        lo = bisect_left(starts, a)
        while lo > 0 and reach[lo - 1] > a:  # spans that began before the edge
            lo -= 1
        cand = [(n, s, e) for s, e, n in mine[lo:bisect_left(starts, b)]]
        for group, secs in innermost(cand, a, b, kind).items():
            add(f"{kind}|{group}", secs)
        add(kind, b - a)

    workers = {r.tid for r in recs if r.name == "device.plane.dispatch"}
    background: dict[int, list] = {}
    waiting: dict[int, list] = {}  # the part of it spent waiting for the device
    for r in recs:
        if _MARSHAL_RE.match(r.name):
            add("marshal", r.dur)
        elif _SYNC_RE.match(r.name):
            add("sync", r.dur)
        if r.name.startswith("gc."):
            add("gc", r.dur)
            add(r.name, r.dur)  # by generation, for the log line
        elif r.tid != tid and r.tid not in workers:
            background.setdefault(r.tid, []).append((r.ts, r.ts + r.dur))
            if _WAIT_RE.match(r.name):
                waiting.setdefault(r.tid, []).append((r.ts, r.ts + r.dur))
    add("background", sum(_union(v) for v in background.values()))
    add("background_waiting", sum(_union(v) for v in waiting.values()))
    return {k: v * 1e3 / blocks for k, v in sums.items()}


def of(ctx):
    """``split`` of this run, computed once and kept on ``ctx``; the whole
    split goes to standard error once, for the reader of a run's log."""
    if "program_spans" not in ctx.__dict__:
        try:
            from fisco_bcos_tpu.observability import tracer
        except ImportError:
            ctx.program_spans = None
            return None
        TRACER = tracer.TRACER
        ring = TRACER.spans()
        ctx.program_spans = split(
            ring, ctx.spans.rows, ctx.t0, ctx.t1,
            int(getattr(ctx.cell, "window_blocks", 0)), threading.get_ident(),
        )
        if ctx.program_spans is not None and hasattr(tracer, "install_gc_spans"):
            ctx.program_spans.setdefault("gc", 0.0)  # hooked, and no pause
        in_window = sum(1 for r in ring if ctx.t0 <= r.ts < ctx.t1)
        print(f"program spans, ms per block: {ctx.program_spans} (ring {len(ring)} of "
              f"{TRACER.capacity}, {in_window} in the window, dropped "
              f"{TRACER.drop_counts()})", file=sys.stderr, flush=True)
    return ctx.program_spans


def read(ctx, *keys: str):
    """Sum of the named parts of the split; None where the ring was too
    young or the program wrote none of them."""
    parts = of(ctx)
    if parts is None or not any(k in parts for k in keys):
        return None
    return sum(parts.get(k, 0.0) for k in keys)

"""The stage clock of the node's spans, read for the window: what is inside a
block's execution, its 2PC and its admission, where the collector's pauses
fell, and which stage the host was in while the chip had nothing outstanding.

A span of the program marks its stages where they happen (``span.stage(name)``
in ``fisco_bcos_tpu/observability/tracer.py``): the seconds since its previous
mark go to ``name``, and the span's one record closes with the sums as its
``stages`` attribute, each also added to
``fisco_span_stage_seconds_total{span,stage}``. A quantity here is the
window's delta of that counter, summed from the increments the records carry:
the drivers take no snapshot of it, and the ring has timestamps.

The rule is ``program_spans.py``'s: the ring's records with ``t0 <= ts < t1``
on any thread, ``derived`` ones left out; per block = over ``window_blocks``,
so a chain cell's number is four replicas'. Every reader gives None where the
ring is younger than ``t0``, where no record of the window carries ``stages``
(a program without the stage clock), or where the cell writes none of the
quantity's span (the catch-up cell admits nothing in its window).

- ``scheduler.execute_block``: ``exec_loop`` (mark ``execute``),
  ``exec_state_root`` (``stateRoot``), ``exec_txs_root`` (``txsRoot``),
  ``exec_receipts_root`` (``receiptsRoot``), ``exec_roots_wait`` (``roots``,
  and the ``roots`` mark of a ``scheduler.commit_block`` that synced them
  lazily: ``roots_under_commit`` in the log line, outside the identity),
  ``exec_other`` (the spans' durations less the five: the fill, the cache hit,
  the state commitment, the tail). The six less ``roots_under_commit`` sum to
  the spans' durations.
- ``scheduler.commit_block``: ``commit_prewrite``, ``commit_prepare``,
  ``commit_write`` (mark ``commit``), ``commit_book`` (the durations less the
  three: the gate, the booking tail). The four sum to the spans' durations.
- ``txpool.submit_batch`` on all nodes: ``admit_static``, ``admit_verify``,
  ``admit_insert``; ``admit_gossip`` = ``txsync.push``'s ``decode`` plus
  ``txsync.maintain``'s time outside its direct children.
- ``gc_in_execute`` / ``_commit`` / ``_admission``: the ``gc.gen*`` records cut
  with the intervals of those spans on the thread the pause interrupted
  (admission = ``txpool.submit_batch``, ``txsync.maintain``, ``txsync.push``).
- ``idle_in_execute`` / ``_commit`` / ``_admission`` / ``idle_elsewhere``: the
  window less the union, over all threads, of the ``device.<op>.enqueue`` and
  ``device.<op>.sync`` records (from a call's dispatch to its answer the
  program knows the chip has work), cut with the same spans' intervals on the
  driving thread. The four sum to the window's idle time. The proof plane's
  trees (``device.merkle_tree.*``: ``BACKGROUND_OPS``) are left out of the
  union: a tree is hashed level by level on the plane worker beside the next
  block, and its records are that thread waiting for the interpreter (186 ms
  of a flood block around some 2 ms of device programs: PERF.md section 6,
  PR 36), not the chip at work.

The log line (``stage parts, ms per block: {...}``) carries beside them what
the identities are held against (``sum:<span>``: the window's durations of a
span; ``window``) and which operation's records cover the time that is not
idle (``outstanding:<op>``).
"""

from __future__ import annotations

import re
import sys
import threading

from benchmark import program_spans

EXECUTE, COMMIT = "scheduler.execute_block", "scheduler.commit_block"
SUBMIT, PUSH, MAINTAIN = "txpool.submit_batch", "txsync.push", "txsync.maintain"
ADMISSION = (SUBMIT, PUSH, MAINTAIN)
_OUTSTANDING_RE = re.compile(r"^device\.[^.]+\.(enqueue|sync)$")
BACKGROUND_OPS = ("merkle_tree",)  # in the log line, not in the union


def _merged(intervals) -> list[tuple[float, float]]:
    """The union of ``intervals`` as sorted disjoint intervals."""
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        elif e > s:
            out.append((s, e))
    return out


def _common(a, b) -> float:
    """Seconds that lie in both of two sorted disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _stages(r) -> dict:
    return getattr(r, "attrs", None) and r.attrs.get("stages") or {}


def split(records, t0: float, t1: float, blocks: int, tid: int):
    """-> per-block milliseconds of every quantity, or None (ring too young,
    no block, or no record with ``stages``). ``tid`` is the driving thread."""
    recs = program_spans.window_records(records, t0, t1)
    if recs is None or blocks <= 0 or not any(_stages(r) for r in recs):
        return None
    dur: dict[str, float] = {}
    marks: dict[str, dict[str, float]] = {}
    maintains = set()
    for r in recs:
        if r.name in (EXECUTE, COMMIT, SUBMIT, PUSH, MAINTAIN):
            dur[r.name] = dur.get(r.name, 0.0) + r.dur
            into = marks.setdefault(r.name, {})
            for stage, secs in _stages(r).items():
                into[stage] = into.get(stage, 0.0) + secs
            if r.name == MAINTAIN:
                maintains.add(r.span_id)
    out: dict[str, float] = {}

    if EXECUTE in dur:
        m, lazy = marks[EXECUTE], marks.get(COMMIT, {}).get("roots", 0.0)
        five = {part: m.get(stage, 0.0) for part, stage in (
            ("exec_loop", "execute"), ("exec_state_root", "stateRoot"),
            ("exec_txs_root", "txsRoot"), ("exec_receipts_root", "receiptsRoot"),
            ("exec_roots_wait", "roots"))}
        out.update(five, exec_other=dur[EXECUTE] - sum(five.values()), roots_under_commit=lazy)
        out["exec_roots_wait"] += lazy
    if COMMIT in dur:
        m = marks[COMMIT]
        three = {part: m.get(stage, 0.0) for part, stage in (
            ("commit_prewrite", "prewrite"), ("commit_prepare", "prepare"),
            ("commit_write", "commit"))}
        out.update(three, commit_book=dur[COMMIT] - sum(three.values()))
    if SUBMIT in dur:
        m = marks[SUBMIT]
        out.update(admit_static=m.get("static", 0.0), admit_verify=m.get("verify", 0.0),
                   admit_insert=m.get("insert", 0.0))
    if PUSH in dur or MAINTAIN in dur:
        children = sum(r.dur for r in recs if r.parent_id in maintains)
        out["admit_gossip"] = (
            marks.get(PUSH, {}).get("decode", 0.0) + dur.get(MAINTAIN, 0.0) - children)

    def spans_of(names, thread) -> list:
        return _merged((max(r.ts, t0), min(r.ts + r.dur, t1)) for r in recs
                       if r.name in names and r.tid == thread)

    kinds = {"execute": (EXECUTE,), "commit": (COMMIT,), "admission": ADMISSION}
    pauses: dict[int, list] = {}
    for r in recs:
        if r.name.startswith("gc."):
            pauses.setdefault(r.tid, []).append((r.ts, r.ts + r.dur))
    for kind, names in kinds.items():
        out[f"gc_in_{kind}"] = sum(
            _common(_merged(held), spans_of(names, thread)) for thread, held in pauses.items())

    calls: dict[str, list] = {}  # op -> its enqueue and sync records
    for r in recs:
        if _OUTSTANDING_RE.match(r.name):
            calls.setdefault(r.name.split(".")[1], []).append(
                (max(r.ts, t0), min(r.ts + r.dur, t1)))
    outstanding = _merged(
        iv for op, held in calls.items() if op not in BACKGROUND_OPS for iv in held)
    idle, cursor = [], t0
    for s, e in outstanding + [(t1, t1)]:
        if s > cursor:
            idle.append((cursor, s))
        cursor = max(cursor, e)
    for kind, names in kinds.items():
        out[f"idle_in_{kind}"] = _common(idle, spans_of(names, tid))
    # the three kinds of span never overlap on one thread; the floor is for rounding
    out["idle_elsewhere"] = max(0.0, sum(e - s for s, e in idle) - sum(
        out[f"idle_in_{kind}"] for kind in kinds))
    # for the log line: what the identities are held against, and which
    # operation's records cover the time that is not idle
    out.update({"sum:" + name: secs for name, secs in dur.items()}, window=t1 - t0)
    out.update({"outstanding:" + op: sum(e - s for s, e in _merged(held))
                for op, held in calls.items()})
    return {k: v * 1e3 / blocks for k, v in out.items()}


def of(ctx):
    """``split`` of this run, computed once and kept on ``ctx``; the whole of
    it goes to standard error once, with the inside view of the idle share."""
    if "stage_parts" not in ctx.__dict__:
        try:
            from fisco_bcos_tpu.observability.tracer import TRACER
        except ImportError:
            ctx.stage_parts = None
            return None
        blocks = int(getattr(ctx.cell, "window_blocks", 0))
        ctx.stage_parts = split(TRACER.spans(), ctx.t0, ctx.t1, blocks, threading.get_ident())
        if ctx.stage_parts is not None:
            idle = sum(v for k, v in ctx.stage_parts.items() if k.startswith("idle_"))
            share = 100.0 * idle / ctx.stage_parts["window"]
            print(f"stage parts, ms per block: {ctx.stage_parts} (idle inside: "
                  f"{share:.2f} % of the window)", file=sys.stderr, flush=True)
    return ctx.stage_parts


def read(ctx, quantity: str):
    """``exec_loop_ms_per_block`` -> the part ``exec_loop``, or None."""
    parts = of(ctx)
    return None if parts is None else parts.get(quantity.removesuffix("_ms_per_block"))

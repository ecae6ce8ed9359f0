"""Critical-path analysis: stitch one transaction's lifecycle spans —
across traces and processes — into an ordered stage breakdown.

The question PR 1's histograms could not answer: *where did THIS
transaction's wall time go?* A transaction's latency crosses three
boundaries that break naive per-trace grouping:

1. **The service split** — the RPC front door, node core, executor and
   storage services are separate processes; the submit trace starts in the
   RPC process and continues in the node via the traceparent field on
   service-RPC frames.
2. **The pool** — between admission and sealing the tx just *waits*; the
   sealer emits a retroactive ``txpool.pool_wait`` span into the tx's trace
   when it finally picks it up.
3. **The block** — from seal onward the tx's fate is the block's: PBFT
   phases, execution, 2PC commit are per-block spans in the block's own
   trace (one per process observing that block). This module keeps the
   tx→block and block→trace_id indexes that let the stitcher pull those in.

``stitch`` = tx-trace spans ∪ block-trace spans ∪ spans link-referencing
either (the device-plane merged batch), ordered by wall time.
``analyze`` names the dominant stage — the artifact
``GET /trace/tx/<hash>`` serves.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Callable

from ..utils.log import note_swallowed
from .tracer import TRACER, SpanRecord, TraceContext

# bounded tx lifecycle index: tx hash hex -> {ctx, t_admit, wall_admit,
# block, committed}. Written at admission, sealed, committed; read by the
# /trace/tx endpoint. Bounded like the span ring — an evicted entry means
# "trace expired", the same answer the ring gives.
_TX_CAP = 16384
_BLOCK_CAP = 1024
# miss-reason memory (ISSUE 9 satellite): a /trace/tx miss distinguishes
# "unsampled" (head sampling dropped the tx at admission — it was seen) and
# "evicted" (the bounded index overwrote it) from a plain "unknown" hash,
# so operators stop chasing sampled-out transactions. Both are bounded
# rings themselves; falling off THEM degrades the answer to "unknown".
_MISS_CAP = 16384

_lock = threading.Lock()
_tx_index: "OrderedDict[str, dict]" = OrderedDict()
_block_index: "OrderedDict[int, list[int]]" = OrderedDict()
_unsampled: "OrderedDict[str, bool]" = OrderedDict()
_evicted: "OrderedDict[str, bool]" = OrderedDict()

# optional extra span providers (other processes' rings): callables
# (trace_ids:set[int], block:int|None) -> list[span dicts]. Node boot can
# register remote executor fleets here.
SPAN_SOURCES: list[Callable] = []


def reset() -> None:
    clear_indexes()
    del SPAN_SOURCES[:]


def clear_indexes() -> None:
    """Drop the tx/block/miss indexes but keep registered SPAN_SOURCES."""
    with _lock:
        _tx_index.clear()
        _block_index.clear()
        _unsampled.clear()
        _evicted.clear()


def note_tx(tx_hash: bytes, ctx: TraceContext | None) -> None:
    """Register a freshly-admitted transaction's trace context."""
    note_txs((tx_hash,), ctx)


def note_txs(tx_hashes, ctx: TraceContext | None) -> None:
    """Batch registration — one lock pass, one timestamp, for the admission
    hot loop (a 15k-tx batch must not pay 15k lock cycles here). Txs whose
    trace was head-sampled out (or whose tracer is off) are remembered in
    the bounded unsampled ring so a later miss can say WHY."""
    if ctx is None or not ctx.sampled:
        # only a LIVE tracer's sampling decision is worth remembering:
        # with the tracer off (FISCO_TELEMETRY=0 — the bench overhead
        # A/B's zero-telemetry leg) this must stay the pre-change early
        # return, not per-tx ring bookkeeping, and a later miss honestly
        # answers "unknown" because nothing was traced at all
        if not TRACER.enabled:
            return
        with _lock:
            for h in tx_hashes:
                _unsampled[h.hex()] = True
            while len(_unsampled) > _MISS_CAP:
                _unsampled.popitem(last=False)
        return
    t_admit = time.perf_counter()
    wall = time.time()
    with _lock:
        for h in tx_hashes:
            _tx_index[h.hex()] = {
                "ctx": ctx,
                "t_admit": t_admit,
                "wall_admit": wall,
                "block": None,
                "committed": None,
            }
        while len(_tx_index) > _TX_CAP:
            key, _entry = _tx_index.popitem(last=False)
            _evicted[key] = True
        while len(_evicted) > _MISS_CAP:
            _evicted.popitem(last=False)


# pool-wait spans are per-tx: cap them per block so a 15k-tx production
# block costs at most this many ring slots (the index still maps every tx)
POOL_WAIT_SPAN_CAP = 1024


def note_sealed(tx_hashes, number: int) -> list[TraceContext]:
    """A proposal picked these txs up: close each tx's pool-wait gap with a
    retroactive span in ITS trace and bind tx -> block. Returns the sealed
    txs' DISTINCT admission contexts (the sealer links its seal span to
    them) — batch-admitted txs all share their batch span's context, so a
    1000-tx batch contributes one pool_wait span and one link, not 1000."""
    now = time.perf_counter()
    ctxs: dict[tuple[int, int], TraceContext] = {}
    waits: list[tuple[TraceContext, float]] = []
    # ONE lock pass over the sealed set (this runs on the sealer's
    # proposal-generation path — per-hash lock churn at 15k txs is real),
    # span emission outside it
    with _lock:
        for h in tx_hashes:
            entry = _tx_index.get(h.hex())
            if entry is None:
                continue
            entry["block"] = number
            ctx: TraceContext = entry["ctx"]
            if (ctx.trace_id, ctx.span_id) in ctxs:
                continue
            # cap BOTH the emitted pool_wait spans and the returned link
            # set: 15k individually-admitted txs must not hang 15k links
            # on the seal span (tx -> block binding above still runs for
            # every hash)
            if len(ctxs) >= POOL_WAIT_SPAN_CAP:
                continue
            ctxs[(ctx.trace_id, ctx.span_id)] = ctx
            waits.append((ctx, entry["t_admit"]))
    for ctx, t_admit in waits:
        TRACER.record(
            "txpool.pool_wait",
            t0=t_admit,
            dur=now - t_admit,
            parent_ctx=ctx,
            derived=True,  # admitted -> sealed: a gap, nobody's work
            block=number,
        )
    return list(ctxs.values())


def note_block_trace(number: int, trace_id: int | None) -> None:
    """Bind a block number to a trace id (one per block trace this process
    opened: the leader's seal, each engine's in-flight cache)."""
    if not trace_id:
        return
    with _lock:
        ids = _block_index.setdefault(number, [])
        if trace_id not in ids:
            ids.append(trace_id)
        while len(_block_index) > _BLOCK_CAP:
            _block_index.popitem(last=False)


def note_committed(tx_hashes, number: int) -> None:
    now = time.time()
    with _lock:  # one pass: this sits on the block-commit txpool drop path
        for h in tx_hashes:
            entry = _tx_index.get(h.hex())
            if entry is not None:
                entry["committed"] = now


def block_trace_ids(number: int) -> list[int]:
    with _lock:
        return list(_block_index.get(number, ()))


# -- span selection / serialization ------------------------------------------


def _span_dict(rec: SpanRecord, epoch: float, pid: int) -> dict:
    return {
        "name": rec.name,
        "wall": rec.ts + epoch,
        "dur": rec.dur,
        "pid": pid,
        "tid": rec.tid,
        "trace_id": f"{rec.trace_id:032x}",
        "span_id": f"{rec.span_id:016x}",
        "parent_id": f"{rec.parent_id:016x}" if rec.parent_id is not None else None,
        "links": [f"{t:032x}:{s:016x}" for t, s in rec.links],
        "attrs": {k: str(v) for k, v in rec.attrs.items()},
    }


# spans that are per-TRANSACTION even though they carry a block attr: the
# block-number match below must not pull OTHER txs' copies into this tx's
# path (their pool waits would skew t0/total/dominant toward a stranger)
_TX_SCOPED_SPANS = frozenset({"txpool.pool_wait"})


def local_spans_for(trace_ids: set[int], block: int | None = None) -> list[dict]:
    """This process's ring spans belonging to the stitched set: trace-id
    members, per-block STAGE spans, and spans LINKING into the set (the
    device-plane merged batch linking absorbed callers)."""
    import os

    pid = os.getpid()
    out = []
    block_s = str(block) if block is not None else None
    for rec in TRACER.spans():
        if rec.trace_id in trace_ids:
            out.append(_span_dict(rec, TRACER.epoch, pid))
        elif (
            block_s is not None
            and rec.name not in _TX_SCOPED_SPANS
            and str(rec.attrs.get("block")) == block_s
        ):
            out.append(_span_dict(rec, TRACER.epoch, pid))
        elif rec.links and any(t in trace_ids for t, _s in rec.links):
            out.append(_span_dict(rec, TRACER.epoch, pid))
    return out


def collect(tx_hash_hex: str) -> dict:
    """Node-side raw collection for one tx: index facts + every local span
    in the stitched set + whatever the registered SPAN_SOURCES add. The
    split-mode RPC process merges ITS local spans into this before
    analyzing (service/rpc_service.py RemoteTelemetry.trace_tx)."""
    key = tx_hash_hex.lower().removeprefix("0x")
    with _lock:
        entry = _tx_index.get(key)
        if entry is None:
            # structured miss (ISSUE 9 satellite): unknown ≠ unsampled ≠
            # evicted — each sends the operator somewhere different
            if key in _unsampled:
                reason, detail = (
                    "unsampled",
                    "head sampling dropped this tx at admission "
                    "(FISCO_TRACE_SAMPLE) — raise the rate to trace it",
                )
            elif key in _evicted:
                reason, detail = (
                    "evicted",
                    "the bounded lifecycle index overwrote this tx — it was "
                    "traced, but too long ago",
                )
            else:
                reason, detail = (
                    "unknown",
                    "this node never admitted a tx with this hash",
                )
            return {
                "found": False,
                "txHash": key,
                "reason": reason,
                "detail": detail,
                "spans": [],
            }
    ctx: TraceContext = entry["ctx"]
    block = entry["block"]
    trace_ids = {ctx.trace_id}
    if block is not None:
        trace_ids.update(block_trace_ids(block))
    spans = local_spans_for(trace_ids, block)
    for source in list(SPAN_SOURCES):
        try:
            spans.extend(source(set(trace_ids), block))
        except Exception as e:
            # a dead remote ring must not kill the local answer
            note_swallowed("critical_path.span_source", e)
            continue
    return {
        "found": True,
        "txHash": key,
        "block": block,
        "committed": entry["committed"],
        "traceIds": sorted(f"{t:032x}" for t in trace_ids),
        "spans": spans,
    }


def analyze(doc: dict) -> dict:
    """Order a collected span set into the critical path: stages sorted by
    wall start (offsets relative to the first), the dominant stage named,
    and the process fan counted. "Dominant" is judged by SELF time — a
    stage's duration minus its direct children in the set — otherwise an
    umbrella span (pbft.execute_and_checkpoint wraps scheduler.execute_block
    and always outlasts it) would be named instead of the stage doing the
    work. Consumes ``collect`` output; the raw ``spans`` list is dropped
    from the result ("stages" carries every field plus the offsets —
    serializing both doubles the payload)."""
    if not doc.get("found"):
        return doc
    spans = sorted(doc.pop("spans", ()), key=lambda s: s["wall"])
    if not spans:
        return {**doc, "stages": [], "dominant": None, "processes": 0}
    t0 = spans[0]["wall"]
    end = max(s["wall"] + s["dur"] for s in spans)
    stages = [
        {
            "name": s["name"],
            "start_ms": round((s["wall"] - t0) * 1e3, 3),
            "dur_ms": round(s["dur"] * 1e3, 3),
            "pid": s["pid"],
            "trace_id": s["trace_id"],
            "span_id": s["span_id"],
            "parent_id": s["parent_id"],
            "links": s["links"],
            "attrs": s["attrs"],
        }
        for s in spans
    ]
    by_id = {s["span_id"]: s for s in stages}
    children_ms: dict[str, float] = {}
    for s in stages:
        p = by_id.get(s["parent_id"]) if s["parent_id"] is not None else None
        if p is None:
            continue
        # only the portion of the child that temporally NESTS inside the
        # parent counts against its self time: retroactive gap spans
        # (txpool.pool_wait hangs off the admission span but runs AFTER
        # it) must not zero the parent's own work
        lo = max(s["start_ms"], p["start_ms"])
        hi = min(s["start_ms"] + s["dur_ms"], p["start_ms"] + p["dur_ms"])
        if hi > lo:
            children_ms[p["span_id"]] = (
                children_ms.get(p["span_id"], 0.0) + (hi - lo)
            )
    for s in stages:
        s["self_ms"] = round(
            max(0.0, s["dur_ms"] - children_ms.get(s["span_id"], 0.0)), 3
        )
    dominant = max(stages, key=lambda s: s["self_ms"])
    return {
        **doc,
        "stages": stages,
        "total_ms": round((end - t0) * 1e3, 3),
        "dominant": dominant["name"],
        "dominant_ms": dominant["self_ms"],
        "processes": len({s["pid"] for s in spans}),
    }


def trace_tx(tx_hash_hex: str) -> dict:
    """The one-call form (Air mode / in-process): collect + analyze."""
    return analyze(collect(tx_hash_hex))


def aggregate_stage_self_ms(committed_only: bool = True) -> dict:
    """Per-stage self-time totals across ALL sampled txs in the index —
    a window's stage vector.

    The per-exemplar ``trace_tx`` answers "where did THIS tx's time go";
    this aggregates: take the union of every indexed (committed) tx's
    trace ids plus their blocks' trace ids, select the ring's spans once
    (a span shared by many txs — the block's execute span — counts ONCE,
    not per tx), compute self times exactly as :func:`analyze` does, and
    sum by stage name."""
    import os

    with _lock:
        entries = [
            {"ctx": e["ctx"], "block": e["block"], "committed": e["committed"]}
            for e in _tx_index.values()
        ]
    trace_ids: set[int] = set()
    blocks: set[int] = set()
    n_txs = 0
    for e in entries:
        if committed_only and e["committed"] is None:
            continue
        n_txs += 1
        trace_ids.add(e["ctx"].trace_id)
        if e["block"] is not None:
            blocks.add(e["block"])
    with _lock:
        for b in blocks:
            trace_ids.update(_block_index.get(b, ()))
    block_strs = {str(b) for b in blocks}
    pid = os.getpid()
    spans = []
    for rec in TRACER.spans():
        block_attr = rec.attrs.get("block")
        if (
            rec.trace_id in trace_ids
            or (block_attr is not None and str(block_attr) in block_strs)
            or (rec.links and any(t in trace_ids for t, _s in rec.links))
        ):
            spans.append(_span_dict(rec, TRACER.epoch, pid))
    doc = analyze({"found": True, "spans": spans})
    totals: dict[str, dict] = {}
    for s in doc.get("stages", ()):
        t = totals.setdefault(s["name"], {"self_ms": 0.0, "count": 0})
        t["self_ms"] += s["self_ms"]
        t["count"] += 1
    for t in totals.values():
        t["self_ms"] = round(t["self_ms"], 3)
    return {
        "txs": n_txs,
        "blocks": len(blocks),
        "spans": len(spans),
        "stages": totals,
    }

"""The conflict-DAG runner's counters, read for the window: how one replica's
``dag_execute_transactions`` call divides among levelising, running the
levels and checking them.

The driver (``drivers/air4_dag.py``) takes ``snapshot()`` at each edge of the
window, as the harness does with ``counters.py``'s; the readers under
``layers/dag_*.py`` work on the difference, per DAG block and replica (one
observation of ``fisco_executor_batch_latency_ms{mode="dag"}`` is one call):

- ``fisco_executor_dag_levels_total``: dependent levels the blocks were cut into;
- ``fisco_executor_dag_stage_seconds_total{stage}``: ``levelize`` (the
  conflict keys of every transaction and the levels), ``run`` (the levels'
  execution: framed, and member by member), ``validate`` (the pairwise check
  of the read and write sets of every level wider than one);
- ``fisco_executor_dag_conflict_reruns_total``: blocks executed again
  serially because a level's declarations lied (its window delta is one of
  ``correct``'s numbers);
- the sum and count of ``fisco_executor_batch_latency_ms{mode="dag"}`` and the
  sum of ``fisco_executor_batch_txs{mode="dag"}``: the whole call, and the
  transactions it was given.

A counter the program does not have reads None here, and its reader None."""

from __future__ import annotations

STAGES = ("levelize", "run", "validate")


def snapshot() -> dict:
    try:
        from fisco_bcos_tpu.observability import BATCH_BUCKETS
        from fisco_bcos_tpu.utils.metrics import REGISTRY
    except ImportError:
        return {}

    def total(name: str):
        found = REGISTRY.counters_matching(name)
        return sum(found.values()) if found else None

    def dag_series(name: str, **kw) -> tuple[float, int]:
        for labels, (_cum, s, n) in REGISTRY.histogram(name, **kw).snapshot().items():
            if dict(labels).get("mode") == "dag":
                return s, n
        return 0.0, 0

    loop_ms, blocks = dag_series("fisco_executor_batch_latency_ms")
    txs, _ = dag_series("fisco_executor_batch_txs", buckets=BATCH_BUCKETS)
    out = {
        "blocks": blocks, "loop_ms": loop_ms, "txs": txs,
        "levels": total("fisco_executor_dag_levels_total"),
        "reruns": total("fisco_executor_dag_conflict_reruns_total") or 0.0,
    }
    for stage in STAGES:
        seconds = total(f'fisco_executor_dag_stage_seconds_total{{stage="{stage}"}}')
        out[stage + "_ms"] = None if seconds is None else 1e3 * seconds
    return out


def window(cell, key: str):
    """(the window's delta of ``key``, its DAG blocks x replicas) from the
    snapshots the driver left on ``cell``, or None where it took none, no DAG
    block ran in the window or the program has no such counter."""
    before, after = getattr(cell, "dag0", None), getattr(cell, "dag1", None)
    if not before or not after or after.get(key) is None or before.get(key) is None:
        return None
    blocks = after["blocks"] - before["blocks"]
    return (after[key] - before[key], blocks) if blocks > 0 else None


def per_block(ctx, key: str):
    """``key`` per DAG block and replica over the window, or None."""
    got = window(ctx.cell, key)
    return None if got is None else got[0] / got[1]

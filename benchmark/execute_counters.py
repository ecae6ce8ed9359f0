"""The executor's counters: what a block's transaction loop costs, and how
much of it ran inside a run frame.

- ``fisco_executor_batch_latency_ms{mode}`` / ``fisco_executor_batch_txs{mode}``:
  one observation a call of ``execute_transactions`` (``mode="serial"``) or
  ``dag_execute_transactions`` (``"dag"``): a block's loop on one replica.
  ``mode="run"`` is one observation a run frame inside such a call
  (consecutive calls to one registry precompile executed in one frame), so
  it is left out of the sums here;
- ``fisco_executor_run_txs_total``: transactions executed inside run frames.

Both readers (``layers/execute_loop_ms_per_block.py``, ``execute_run_share.py``)
work on the counters' totals **since the process started**: the warm batches
of set-up, the window, the traced blocks and the block of corrupted lanes, in
the catch-up cell also the backlog's three live replicas. Every one of them is
a block of the cell's own mix, so a mean over them is the window's mean to
within the warm batches' first-call costs; a window delta would need the
executor's sums in ``counters.py``'s snapshot, which this file cannot add to.

A program that has none of them gives zeros, and a reader None."""

from __future__ import annotations


def totals() -> dict:
    try:
        from fisco_bcos_tpu.observability import BATCH_BUCKETS
        from fisco_bcos_tpu.utils.metrics import REGISTRY
    except ImportError:
        return {"loop_ms": 0.0, "batches": 0, "txs": 0.0, "run_txs": None}

    def loops(name: str, **kw) -> tuple[float, int]:
        """(sum, observations) of the histogram's series other than ``run``."""
        series = [
            (s, n) for labels, (_cum, s, n) in REGISTRY.histogram(name, **kw).snapshot().items()
            if dict(labels).get("mode") != "run"
        ]
        return sum(s for s, _n in series), sum(n for _s, n in series)

    loop_ms, batches = loops("fisco_executor_batch_latency_ms")
    txs, _ = loops("fisco_executor_batch_txs", buckets=BATCH_BUCKETS)
    run = REGISTRY.counters_matching("fisco_executor_run_txs_total")
    return {"loop_ms": loop_ms, "batches": batches, "txs": txs,
            "run_txs": sum(run.values()) if run else None}

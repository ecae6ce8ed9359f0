"""Sealer, PBFT, scheduler, storage: the share of the window's DAG transactions that
ran as futures on the thread pool (``fisco_executor_dag_pooled_txs_total`` over
the sum of ``fisco_executor_batch_txs{mode="dag"}``): members of a level wider
than one. Rule: ``benchmark/dag_counters.py``."""

from benchmark import dag_counters


def read(ctx):
    pooled, txs = dag_counters.window(ctx.cell, "pooled_txs"), dag_counters.window(ctx.cell, "txs")
    if pooled is None or txs is None or not txs[0]:
        return None
    return 100.0 * pooled[0] / txs[0]

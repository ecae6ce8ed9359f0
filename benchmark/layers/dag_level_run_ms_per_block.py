"""Sealer, PBFT, scheduler, storage: the levels' execution, futures on the thread
pool and single transactions inline
(``fisco_executor_dag_stage_seconds_total{stage="run"}``), one replica's mean a
DAG block of the window. Rule: ``benchmark/dag_counters.py``."""

from benchmark import dag_counters


def read(ctx):
    return dag_counters.per_block(ctx, "run_ms")

"""Sealer, PBFT, scheduler, storage: the levels' execution on the thread that
executes the block: the level frame's precompiled calls, the contract frame's
calls, and single members through ``_execute_one``
(``fisco_executor_dag_stage_seconds_total{stage="run"}``), one replica's mean a
DAG block of the window. Rule: ``benchmark/dag_counters.py``."""

from benchmark import dag_counters


def read(ctx):
    return dag_counters.per_block(ctx, "run_ms")

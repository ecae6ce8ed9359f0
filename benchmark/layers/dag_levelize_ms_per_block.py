"""Sealer, PBFT, scheduler, storage: ``extract_criticals`` of every transaction and
``dag_levels`` (``fisco_executor_dag_stage_seconds_total{stage="levelize"}``),
one replica's mean a DAG block of the window.
Rule: ``benchmark/dag_counters.py``."""

from benchmark import dag_counters


def read(ctx):
    return dag_counters.per_block(ctx, "levelize_ms")

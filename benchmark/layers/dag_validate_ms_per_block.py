"""Sealer, PBFT, scheduler, storage: the pairwise check of the read and write
sets of every level wider than one (``fisco_executor_dag_stage_seconds_total{stage="validate"}``),
one replica's mean a DAG block of the window.
Rule: ``benchmark/dag_counters.py``."""

from benchmark import dag_counters


def read(ctx):
    return dag_counters.per_block(ctx, "validate_ms")

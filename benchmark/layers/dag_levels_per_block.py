"""Sealer, PBFT, scheduler, storage: dependent levels the conflict-DAG runner cut
a block into (``fisco_executor_dag_levels_total``), one replica's mean a DAG
block of the window: 1 where no two transactions share a conflict key, the
block's size where each depends on the one before.
Rule: ``benchmark/dag_counters.py``."""

from benchmark import dag_counters


def read(ctx):
    return dag_counters.per_block(ctx, "levels")

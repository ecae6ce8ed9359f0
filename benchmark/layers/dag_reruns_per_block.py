"""Sealer, PBFT, scheduler, storage: blocks the conflict-DAG runner executed again
serially because a level touched state its declarations called disjoint
(``fisco_executor_dag_conflict_reruns_total``), one replica's mean a DAG block of
the window: 0 where the declarations are honest.
Rule: ``benchmark/dag_counters.py``."""

from benchmark import dag_counters


def read(ctx):
    return dag_counters.per_block(ctx, "reruns")

"""Sealer, PBFT, scheduler, storage: one replica's whole ``dag_execute_transactions``
call (the sum of ``fisco_executor_batch_latency_ms{mode="dag"}``), its mean a
DAG block of the window: levelize + run + validate and what they leave out
(the frames and the shadow overlay set up and merged).
Rule: ``benchmark/dag_counters.py``."""

from benchmark import dag_counters


def read(ctx):
    return dag_counters.per_block(ctx, "loop_ms")

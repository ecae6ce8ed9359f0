"""Sealer, PBFT, scheduler, storage: what the thread that executes the block waited
for its thread pool (``fisco_executor_dag_pool_wait_seconds_total``: inside
``fut.result()`` of every level's futures), one replica's mean a DAG block of
the window: the pooled members' run as that thread sees it, a part of
``dag_level_run_ms_per_block``. None on a program without the counter.
Rule: ``benchmark/contract_counters.py``, ``benchmark/dag_counters.py``."""

from benchmark import contract_counters, dag_counters


def read(ctx):
    waited = contract_counters.window(ctx.cell, "pool_wait_s")
    blocks = dag_counters.window(ctx.cell, "levels")
    return 1e3 * waited / blocks[1] if waited is not None and blocks else None

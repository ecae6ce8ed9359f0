"""Sealer, PBFT, scheduler, storage: the transaction loop of one block on one
replica (``TransactionExecutor.execute_transactions``: every transaction
executed and its receipt built; the three roots are not in it), as the mean of
``fisco_executor_batch_latency_ms`` over every block the process executed. A
chain cell's block pays it once a replica. Rule: ``benchmark/execute_counters.py``."""

from benchmark import execute_counters


def read(ctx):
    t = execute_counters.totals()
    return t["loop_ms"] / t["batches"] if t["batches"] else None

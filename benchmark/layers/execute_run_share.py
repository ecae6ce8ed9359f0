"""Sealer, PBFT, scheduler, storage: the share of executed transactions that
ran inside a run frame (``fisco_executor_run_txs_total`` over the sum of
``fisco_executor_batch_txs``), over every block the process executed. None on
a program without the counter. Rule: ``benchmark/execute_counters.py``."""

from benchmark import execute_counters


def read(ctx):
    t = execute_counters.totals()
    if t["run_txs"] is None or not t["txs"]:
        return None
    return 100.0 * t["run_txs"] / t["txs"]

"""Sealer, PBFT, scheduler, storage: the share of the DAG runner's transactions
that ran inside its level frame, on the thread that executes the block
(``fisco_executor_dag_framed_txs_total`` over the sum of
``fisco_executor_batch_txs{mode="dag"}``): calls to registry precompiles.
Both are the process's totals since it started, as in
``benchmark/execute_counters.py``: ``dag_counters.snapshot()`` names the
counters the driver takes at the window's edges, and this one is not among
them. Every ``mode="dag"`` call of the cell's process is a block of the
cell's own mix (the warm batches, the window, the traced blocks, the block of
corrupted lanes); the opening blocks of set-up are ``mode="serial"`` and stay
out. None on a program without the counter."""

from benchmark import dag_counters


def read(ctx):
    try:
        from fisco_bcos_tpu.utils.metrics import REGISTRY
    except ImportError:
        return None
    framed = REGISTRY.counters_matching("fisco_executor_dag_framed_txs_total")
    txs = dag_counters.snapshot().get("txs")
    if not framed or not txs:
        return None
    return 100.0 * sum(framed.values()) / txs

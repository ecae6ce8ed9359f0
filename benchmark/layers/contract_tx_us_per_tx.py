"""Sealer, PBFT, scheduler, storage: what one deployed contract's call costs inside a
block, on the thread that ran it (``fisco_executor_contract_tx_seconds_total``
over ``fisco_executor_contract_txs_total``, the window's deltas, all four
replicas): the overlay, the account / freeze / ACL gates, the Executive, the
VM's run and the merge. Under the DAG runner's pool a member's seconds
include its waits for the interpreter's lock, which is the reading wanted: it
is what a pooled member costs against an inline one. None on a program
without the counters. Rule: ``benchmark/contract_counters.py``."""

from benchmark import contract_counters


def read(ctx):
    seconds = contract_counters.window(ctx.cell, "contract_tx_s")
    txs = contract_counters.window(ctx.cell, "contract_txs")
    return 1e6 * seconds / txs if seconds is not None and txs else None

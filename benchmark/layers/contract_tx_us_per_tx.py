"""Sealer, PBFT, scheduler, storage: what one deployed contract's call costs inside a
block, on the thread that ran it (``fisco_executor_contract_tx_seconds_total``
over ``fisco_executor_contract_txs_total``, the window's deltas, all four
replicas): in the batch's contract frame (PR 42) the frame's overlay, the
gates looked up once a callee, the native engine's run and the rows moved
down; through ``_execute_one`` an overlay, the account / freeze / ACL gates,
the Executive, the VM's run and the merge. None on a program without the
counters. Rule: ``benchmark/contract_counters.py``."""

from benchmark import contract_counters


def read(ctx):
    seconds = contract_counters.window(ctx.cell, "contract_tx_s")
    txs = contract_counters.window(ctx.cell, "contract_txs")
    return 1e6 * seconds / txs if seconds is not None and txs else None

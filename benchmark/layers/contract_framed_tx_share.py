"""Sealer, PBFT, scheduler, storage: the share of the window's deployed-contract
calls that executed in the batch's contract frame
(``fisco_executor_contract_framed_txs_total`` over
``fisco_executor_contract_txs_total``, the window's deltas, all four replicas):
one overlay a batch, the native engine bound once, the gates looked up once a
callee (PR 42). 100 in ``air4-parallelok.flood``; a member the frame stood
aside for (a create, no native engine, a run the engine escaped from) went
through ``_execute_one`` and counts against it. None on a program without the
counter. Rule: ``benchmark/contract_counters.py``."""

from benchmark import contract_counters


def read(ctx):
    framed = contract_counters.window(ctx.cell, "contract_framed")
    txs = contract_counters.window(ctx.cell, "contract_txs")
    return 100.0 * framed / txs if framed is not None and txs else None

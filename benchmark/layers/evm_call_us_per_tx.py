"""Sealer, PBFT, scheduler, storage: the seconds a deployed contract's call spends
inside the VM (``fisco_executor_evm_seconds_total`` over the top-level frames
of ``fisco_executor_evm_calls_total``, the window's deltas, all four
replicas): the native engine's run with its ``sload`` / ``sstore`` callbacks
into the interpreter, or the Python loop. Beside ``contract_tx_us_per_tx`` it
says how much of a call is the engine and how much the frame around it. None
on a program without the counters. Rule: ``benchmark/contract_counters.py``."""

from benchmark import contract_counters


def read(ctx):
    seconds = contract_counters.window(ctx.cell, "evm_s")
    calls = contract_counters.evm_calls(ctx.cell)
    return 1e6 * seconds / calls if seconds is not None and calls else None

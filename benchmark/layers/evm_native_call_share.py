"""Sealer, PBFT, scheduler, storage: the share of the window's top-level EVM frames
that the native engine finished
(``fisco_executor_evm_calls_total{engine="native"}`` over both engines'): 100
where no frame escaped to the Python interpreter, which ``correct`` requires
of the deployed-contract cell. None on a program without the counter.
Rule: ``benchmark/contract_counters.py``."""

from benchmark import contract_counters


def read(ctx):
    native = contract_counters.window(ctx.cell, "evm_native")
    calls = contract_counters.evm_calls(ctx.cell)
    return 100.0 * native / calls if native is not None and calls else None

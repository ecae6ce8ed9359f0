"""The contract leg's counters, read for the window: what a deployed
contract's call costs inside a block, how many of them ran in the batch's
contract frame, and which engine ran them.

The driver (``drivers/air4_parallelok.py``) takes ``snapshot()`` at each edge
of the window, beside ``dag_counters.py``'s; the readers
``layers/contract_tx_us_per_tx.py``, ``evm_call_us_per_tx.py``,
``evm_native_call_share.py`` and ``contract_framed_tx_share.py`` work on the
difference. The program adds to each once a batch (a DAG call, a serial
batch), from sums its members carried back with their results:

- ``fisco_executor_contract_txs_total`` / ``..._contract_tx_seconds_total``:
  block transactions whose callee is no registry precompile, and the seconds
  each took, from the contract frame's entry to its receipt or inside
  ``_execute_one``, on the thread that executes the block;
  ``fisco_executor_contract_framed_txs_total``: those of them executed in the
  batch's contract frame;
- ``fisco_executor_evm_calls_total{engine="native"|"interpreter"}``: those
  transactions' top-level frames by the engine that finished them (the window's
  delta of ``native`` is one of ``correct``'s numbers: a call the Python
  interpreter ran or resumed is a wrong result there, not a slow one);
  ``fisco_executor_evm_seconds_total``: their seconds inside the VM.

``pool_wait_s`` (``fisco_executor_dag_pool_wait_seconds_total``: 0 since PR 41
took the runner's pool out) has no reader here any more; the key stays in the
snapshot because ``tests/test_contract_dag_block.py``, which is not the
benchmark's to edit, reads it there, and goes with the program's counter.

A counter the program does not have reads None here, and its reader None."""

from __future__ import annotations

COUNTERS = {
    "contract_txs": "fisco_executor_contract_txs_total",
    "contract_framed": "fisco_executor_contract_framed_txs_total",
    "contract_tx_s": "fisco_executor_contract_tx_seconds_total",
    "evm_native": 'fisco_executor_evm_calls_total{engine="native"}',
    "evm_interpreter": 'fisco_executor_evm_calls_total{engine="interpreter"}',
    "evm_s": "fisco_executor_evm_seconds_total",
    "pool_wait_s": "fisco_executor_dag_pool_wait_seconds_total",
}


def snapshot() -> dict:
    try:
        from fisco_bcos_tpu.utils.metrics import REGISTRY
    except ImportError:
        return {}
    out = {}
    for key, name in COUNTERS.items():
        found = REGISTRY.counters_matching(name)
        out[key] = sum(found.values()) if found else None
    return out


def window(cell, key: str):
    """The window's delta of ``key`` from the snapshots the driver left on
    ``cell``, or None where it took none or the program has no such counter
    (one that first appears inside the window counts from 0)."""
    before, after = getattr(cell, "contract0", None), getattr(cell, "contract1", None)
    if not before or not after or after.get(key) is None:
        return None
    return after[key] - (before.get(key) or 0.0)


def evm_calls(cell):
    """The window's top-level EVM frames, both engines, or None."""
    native, interpreted = window(cell, "evm_native"), window(cell, "evm_interpreter")
    return None if native is None or interpreted is None else native + interpreted

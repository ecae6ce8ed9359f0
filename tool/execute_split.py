#!/usr/bin/env python3
"""What is inside ``scheduler.execute_block`` in a chain cell, per block.

    python3 tool/execute_split.py --workload air4-transfer.flood --seed <n> [--seconds 51]

One ``--trace 1`` run of the cell through ``benchmark/run.py``'s own ``run``
(through the chip tool; exit 4 off the chip), with a wall clock around the
five calls a block's execution makes: the transaction loop
(``TransactionExecutor.execute_transactions``), the state root's dispatch
(``get_hash_async``: preimages and the batch's enqueue), the two roots' host
parts (``Block.calculate_txs_root_async``, ``calculate_receipts_root_async``)
and ``Scheduler._execute_block_locked`` around them. Calls that started inside
the window are summed and divided by the window's blocks, so a chain cell's
number is four replicas' and the catch-up cell's one replica's, as
``seal_execute_ms_per_block`` is; the tracer's ``executor.execute`` and
``executor.run`` spans of the window are summed beside them. The wrappers cost
two clock readings a call, twenty calls a block.

Last line of standard output: the run's result line with ``execute_split``
(ms per block) added."""

from __future__ import annotations

import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CALLS: dict[str, list[tuple[float, float]]] = {}


def _timed(owner, attr: str, label: str) -> None:
    inner = getattr(owner, attr)
    rows = CALLS.setdefault(label, [])

    def wrapper(*args, **kwargs):
        t = time.perf_counter()
        try:
            return inner(*args, **kwargs)
        finally:
            rows.append((t, time.perf_counter() - t))

    setattr(owner, attr, wrapper)


def main(argv=None) -> int:
    from benchmark import manifest, run

    args = run.parse((argv if argv is not None else sys.argv[1:]) + ["--trace", "1"])
    from fisco_bcos_tpu.executor.executor import TransactionExecutor
    from fisco_bcos_tpu.observability.tracer import TRACER
    from fisco_bcos_tpu.protocol.block import Block
    from fisco_bcos_tpu.scheduler.scheduler import Scheduler

    _timed(Scheduler, "_execute_block_locked", "execute_block")
    _timed(TransactionExecutor, "execute_transactions", "loop")
    _timed(TransactionExecutor, "get_hash_async", "state_root_dispatch")
    _timed(Block, "calculate_txs_root_async", "txs_root_dispatch")
    _timed(Block, "calculate_receipts_root_async", "receipts_root_dispatch")

    seen = {}
    real_driver_of = manifest.driver_of

    def driver_of(config):
        """The cell's driver, with the cell it builds kept for the split."""
        module = real_driver_of(config)

        class Kept(module.Cell):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                seen["cell"] = self

        return types.SimpleNamespace(Cell=Kept)

    manifest.driver_of = driver_of
    line = run.run(args)
    cell = seen["cell"]
    t0, t1, blocks = cell.t0, cell.t1, int(cell.window_blocks)
    split = {
        label: sum(d for t, d in rows if t0 <= t < t1) * 1e3 / blocks
        for label, rows in CALLS.items()
    }
    ring = [r for r in TRACER.spans() if t0 <= r.ts < t1]
    for name in ("scheduler.execute_block", "executor.execute", "executor.run"):
        spans = [r for r in ring if r.name == name]
        split["span:" + name] = sum(r.dur for r in spans) * 1e3 / blocks
        split["n:" + name] = len(spans) / blocks
    split["rest_of_execute_block"] = split["execute_block"] - sum(
        split[k] for k in ("loop", "state_root_dispatch", "txs_root_dispatch",
                           "receipts_root_dispatch"))
    split["window_blocks"] = blocks
    line["execute_split"] = {k: round(v, 3) for k, v in split.items()}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)

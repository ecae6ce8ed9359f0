"""Sealer, PBFT, scheduler, storage: the receipts root's host part
(``scheduler.execute_block``'s stage ``receiptsRoot``: the receipts finished and
hashed, the tree's enqueue), every replica's, over the window, per block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "exec_receipts_root_ms_per_block")

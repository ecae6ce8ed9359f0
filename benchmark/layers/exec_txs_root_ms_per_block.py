"""Sealer, PBFT, scheduler, storage: the transactions root's host part
(``scheduler.execute_block``'s stage ``txsRoot``: the leaves and the tree's
enqueue), every replica's, over the window, per block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "exec_txs_root_ms_per_block")

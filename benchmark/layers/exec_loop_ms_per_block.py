"""Sealer, PBFT, scheduler, storage: the transaction loop of a block's executions
(``scheduler.execute_block``'s stage ``execute``: ``next_block_header`` and the
DAG and serial batches), every replica's, over the window, per block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "exec_loop_ms_per_block")

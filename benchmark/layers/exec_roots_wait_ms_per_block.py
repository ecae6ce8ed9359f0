"""Sealer, PBFT, scheduler, storage: waiting for a block's three roots (the stage
``roots`` of ``scheduler.execute_block``, and of a ``scheduler.commit_block``
that synced them lazily), every replica's, over the window, per block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "exec_roots_wait_ms_per_block")

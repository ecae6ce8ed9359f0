"""Sealer, PBFT, scheduler, storage: the ledger's rows of a block
(``scheduler.commit_block``'s stage ``prewrite``: ``ledger.prewrite_block``),
every replica's, over the window, per block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "commit_prewrite_ms_per_block")

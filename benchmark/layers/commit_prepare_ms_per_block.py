"""Sealer, PBFT, scheduler, storage: the 2PC's first leg
(``scheduler.commit_block``'s stage ``prepare``), every replica's, over the
window, per block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "commit_prepare_ms_per_block")

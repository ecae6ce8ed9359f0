"""Sealer, PBFT, scheduler, storage: ``scheduler.commit_block``'s durations less
its prewrite and two legs: the gate (waiting for the prior commit) and the
booking tail under the lock (the pool's ``on_block_committed``, ``promote``, the
notify posts); every replica's, per block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "commit_book_ms_per_block")

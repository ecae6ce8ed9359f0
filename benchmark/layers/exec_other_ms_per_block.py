"""Sealer, PBFT, scheduler, storage: ``scheduler.execute_block``'s durations less
its loop, three root dispatches and roots' wait: the lock and the fill, a cache
hit, the state commitment, the tail under the lock; every replica's, over the
window, per block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "exec_other_ms_per_block")

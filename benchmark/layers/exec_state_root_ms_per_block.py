"""Sealer, PBFT, scheduler, storage: the state root's dispatch
(``scheduler.execute_block``'s stage ``stateRoot``: ``get_hash_async``, the
preimages and the batch's enqueue), every replica's, over the window, per block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "exec_state_root_ms_per_block")

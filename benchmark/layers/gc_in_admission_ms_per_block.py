"""Node process: collector pauses (``gc.gen*``) that interrupted
``txpool.submit_batch``, ``txsync.maintain`` or ``txsync.push`` on its thread,
over the window, per block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "gc_in_admission_ms_per_block")

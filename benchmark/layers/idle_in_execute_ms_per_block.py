"""Device: time the program had no device call outstanding (no
``device.<op>.enqueue`` or ``.sync`` record open on any thread) while the
driving thread was inside a ``scheduler.execute_block``, over the window, per
block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "idle_in_execute_ms_per_block")

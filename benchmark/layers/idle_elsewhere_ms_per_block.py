"""Device: time the program had no device call outstanding while the driving thread
was in none of execution, the 2PC and admission (PBFT, sealing, the driver),
over the window, per block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "idle_elsewhere_ms_per_block")

"""Crypto seam: the mesh leg's ``place`` phase, a block's operands put on the
mesh (one shard of each to its device) and waited for, summed over the window
(``fisco_device_phase_ms{op="admission*_sharded",phase="place"}``) per block.
None on a program whose mesh leg has no such phase. Rule:
``benchmark/mesh_counters.py``."""

from benchmark import mesh_counters


def read(ctx):
    spent = mesh_counters.window(ctx, "place_ms")
    blocks = getattr(ctx.cell, "window_blocks", 0)
    return spent / blocks if spent and blocks else None

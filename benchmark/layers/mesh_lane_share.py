"""Crypto seam: lanes of the window that the sharded admission programs were
given (``fisco_device_items_total{op="admission*_sharded"}``), as a share of
the lanes of all device admission programs. 100 where every block went out
over the mesh; ``device_leg_share`` cannot tell, it reads 100 for one chip
too. Rule: ``benchmark/mesh_counters.py``."""

from benchmark import mesh_counters


def read(ctx):
    sharded, lanes = (mesh_counters.window(ctx, k) for k in ("sharded_lanes", "device_lanes"))
    return 100.0 * sharded / lanes if lanes else None

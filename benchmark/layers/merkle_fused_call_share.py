"""Device programs: the share of the window's merkle calls (a block's roots and the
proof plane's trees, all replicas) that ran as ONE fused device program
(``fisco_device_dispatch_path_total{op="merkle_root"|"merkle_tree",path="fused"}``
over both paths, ``fused`` and ``levels``, written where the merkle span is
entered): 100 on an SM chain of full blocks since PR 45, where a tree of 256
leaves and more is ``jit_tree``; a tree that went level by level pays a
dispatch and a sync a level. None on a program without the counter and where
no tree was hashed. Rule: ``benchmark/sm_counters.py``."""

from benchmark import sm_counters


def read(ctx):
    fused = levels = 0.0
    for op in sm_counters.MERKLE_OPS:
        got = (sm_counters.window(ctx.cell, op, "calls_fused"),
               sm_counters.window(ctx.cell, op, "calls_levels"))
        if None in got:
            return None
        fused, levels = fused + got[0], levels + got[1]
    return 100.0 * fused / (fused + levels) if fused + levels else None

"""TxPool admission + tx gossip: a batch's per-transaction checks and quota
(``txpool.submit_batch``'s stage ``static``), the entry node and the three
replicas, over the window, per block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "admit_static_ms_per_block")

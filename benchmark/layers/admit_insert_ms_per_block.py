"""TxPool admission + tx gossip: the pool inserts, the results, the index, the
persist and the batch's telemetry (``txpool.submit_batch``'s stage ``insert``),
all four nodes, per block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "admit_insert_ms_per_block")

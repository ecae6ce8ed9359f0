"""TxPool admission + tx gossip: the gossip around the replicas' admission:
``txsync.push``'s stage ``decode`` (three replicas decode the batch) and
``txsync.maintain``'s time outside its children (the entry node encodes and
broadcasts it), per block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "admit_gossip_ms_per_block")

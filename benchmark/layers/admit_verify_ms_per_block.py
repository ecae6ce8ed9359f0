"""TxPool admission + tx gossip: ``batch_admit``, its waits for the device included
(``txpool.submit_batch``'s stage ``verify``), the entry node and the three
replicas, over the window, per block.
Rule: ``benchmark/stage_parts.py``."""

from benchmark import stage_parts


def read(ctx):
    return stage_parts.read(ctx, "admit_verify_ms_per_block")

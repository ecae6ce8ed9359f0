"""BlockSync: one replica's execution of a downloaded block with the roots
compared, per applied block (sum of ``fisco_block_execute_latency_ms``: in the
window only the replica that catches up executes).
Rule: ``benchmark/sync_counters.py``."""

from benchmark import sync_counters


def read(ctx):
    return sync_counters.per_block(ctx, "execute_ms")

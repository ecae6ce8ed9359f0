"""BlockSync: one replica's ledger prewrite and 2PC of a downloaded block, per
applied block (sum of ``fisco_block_commit_latency_ms``).
Rule: ``benchmark/sync_counters.py``."""

from benchmark import sync_counters


def read(ctx):
    return sync_counters.per_block(ctx, "commit_ms")

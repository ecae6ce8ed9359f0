"""BlockSync: a response's blocks decoded from their wire bytes into the
download queue, per applied block
(``fisco_sync_stage_seconds_total{stage="decode"}``).
Rule: ``benchmark/sync_counters.py``."""

from benchmark import sync_counters


def read(ctx):
    return sync_counters.per_block(ctx, "decode_ms")

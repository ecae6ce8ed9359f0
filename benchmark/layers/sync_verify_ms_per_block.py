"""BlockSync: a gather's transactions through one admission call (the lists
built, the plane's queue, marshal, the device program, unpack, senders and
hashes filled), every gather of the window, per applied block
(``fisco_sync_stage_seconds_total{stage="verify"}``).
Rule: ``benchmark/sync_counters.py``."""

from benchmark import sync_counters


def read(ctx):
    return sync_counters.per_block(ctx, "verify_ms")

"""BlockSync: the serving peer reading a requested range from its ledger and
encoding it, per block the replica applied
(``fisco_sync_stage_seconds_total{stage="serve_request"}``; with the in-process
gateway it runs in the replica's interpreter, inside the request's send).
Rule: ``benchmark/sync_counters.py``."""

from benchmark import sync_counters


def read(ctx):
    return sync_counters.per_block(ctx, "serve_request_ms")

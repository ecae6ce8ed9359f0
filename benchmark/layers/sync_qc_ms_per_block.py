"""BlockSync: the sealer signatures of a gather's headers as one batch at the
dispatch seam, with the checks of committee and weight before it, per applied
block (``fisco_sync_stage_seconds_total{stage="qc"}``).
Rule: ``benchmark/sync_counters.py``."""

from benchmark import sync_counters


def read(ctx):
    return sync_counters.per_block(ctx, "qc_ms")

"""BlockSync: transactions re-verified per admission call in the window
(``fisco_sync_verify_lanes_total`` over ``fisco_sync_verify_calls_total``).
Near 10,000 says the gathers stayed full across responses; 1,000 would be a
call a block. Rule: ``benchmark/sync_counters.py``."""


def read(ctx):
    before, after = getattr(ctx.cell, "sync0", None), getattr(ctx.cell, "sync1", None)
    if not before or not after or after["calls"] <= before["calls"]:
        return None
    return (after["lanes"] - before["lanes"]) / (after["calls"] - before["calls"])

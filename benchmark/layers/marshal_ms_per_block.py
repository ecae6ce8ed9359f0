"""Crypto seam: host time of the traced ``admit_batch`` calls less the
device's busy time in them, per block: padding, limb splitting, transfer
and the result sync."""


def read(ctx):
    if ctx.red is None or not ctx.cell.traced_series:
        return None
    host_s = sum(s["block_ms"] for s in ctx.cell.traced_series) / 1e3
    return (host_s - ctx.red["busy_s"]) * 1e3 / len(ctx.cell.traced_series)

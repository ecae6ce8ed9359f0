"""Device programs: device time of every program but admission (keccak
batches, the fused merkle tree, state roots) per traced block."""

from benchmark.layers.admission_us_per_sig import admission_seconds


def read(ctx):
    if ctx.red is None or not ctx.cell.traced_series:
        return None
    s = sum(ctx.red["program_s"].values()) - admission_seconds(ctx.red)
    return s * 1e3 / len(ctx.cell.traced_series) if s > 0 else None

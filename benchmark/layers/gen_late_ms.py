"""Client: how late the generator submitted each batch against its due time,
mean over the window. Near 0 when the chain keeps up; it grows when a stall
holds later batches back, or when the generator itself is starved."""

from statistics import fmean


def read(ctx):
    v = [s["late_ms"] for s in ctx.cell.series if "late_ms" in s]
    return fmean(v) if v else None

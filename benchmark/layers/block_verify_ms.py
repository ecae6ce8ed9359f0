"""Crypto seam: host time of one ``admit_batch`` call of a full block, result
on the host, mean over the window."""

from statistics import fmean


def read(ctx):
    d = ctx.spans.durations("bench.admit_batch", ctx.t0, ctx.t1)
    return fmean(d) * 1e3 if d else None

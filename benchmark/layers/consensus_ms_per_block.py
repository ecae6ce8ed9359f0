"""Sealer, PBFT, scheduler, storage: host time from ``seal_and_submit`` to the
block committed on all replicas, mean over the window's blocks."""

from statistics import fmean


def read(ctx):
    d = ctx.spans.durations("bench.seal_and_submit", ctx.t0, ctx.t1)
    return fmean(d) * 1e3 if d else None

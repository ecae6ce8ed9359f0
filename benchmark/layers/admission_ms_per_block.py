"""TxPool admission + tx gossip: host time of one batch's ``submit_batch`` at
the entry node plus ``tx_sync.maintain`` (the three replicas admit inside it),
mean over the window's blocks."""

from statistics import fmean


def read(ctx):
    d = ctx.spans.durations("bench.submit_batch", ctx.t0, ctx.t1)
    return fmean(d) * 1e3 if d else None

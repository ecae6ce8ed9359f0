"""DevicePlane: mean wait of a request from submit to dispatch over the
window, from the sum and count of the plane's queue-wait histogram (exact;
its buckets are too coarse for a median)."""


def read(ctx):
    n = ctx.c1["plane_wait_count"] - ctx.c0["plane_wait_count"]
    if n <= 0:
        return None
    return (ctx.c1["plane_wait_sum_ms"] - ctx.c0["plane_wait_sum_ms"]) / n

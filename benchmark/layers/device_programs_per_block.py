"""Crypto seam: executions of device programs on the busiest device in the
traced window, over the window's blocks (``calls`` of the trace reduction,
read from the XLA Modules line). 1 where a block's admission is one fused
program; 5 where an SM batch went hash → ZA → e → verify → address as
programs of their own."""


def read(ctx):
    blocks = len(getattr(ctx.cell, "traced_series", ()))
    if ctx.red is None or not blocks:
        return None
    return sum(ctx.red["calls"].values()) / blocks

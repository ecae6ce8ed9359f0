"""Device: 1 - union of device-op intervals over the traced window."""


def read(ctx):
    return None if ctx.red is None else 100.0 * ctx.red["idle_share"]

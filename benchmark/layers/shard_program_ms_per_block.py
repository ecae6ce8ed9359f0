"""Device programs: device time of the sharded admission program
(``jit_admission_shard``, one execution a device a block) over the traced
blocks, from the XLA Modules line; the trace reduction's ``program_s`` is the
mean over the devices, so this is one chip's program time a block, the
all_gather of the packed result included."""


def read(ctx):
    blocks = len(getattr(ctx.cell, "traced_series", ()))
    if ctx.red is None or not blocks:
        return None
    s = sum(v for k, v in ctx.red["program_s"].items() if "admission_shard" in k)
    return s * 1e3 / blocks if s > 0 else None

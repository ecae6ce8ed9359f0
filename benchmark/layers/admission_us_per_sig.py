"""Device programs: device time of the admission program's executions in the
traced window over the signatures they were given (``fisco_device_items_total``
for the device admission ops, real lanes, padding not counted). Stands in for
a roofline share until the program has an operation count (PERF.md §3)."""


def admission_seconds(red):
    return sum(v for k, v in red["program_s"].items() if "admission" in k)


def read(ctx):
    if ctx.red is None:
        return None
    s = admission_seconds(ctx.red)
    lanes = ctx.ct1["admission_items"] - ctx.c1["admission_items"]
    return s * 1e6 / lanes if s > 0 and lanes > 0 else None

"""Crypto seam: admission dispatches that took the device leg, as a share of
all admission dispatches in the window
(``fisco_device_dispatch_path_total{op="admission"}``)."""

from benchmark.counters import delta


def read(ctx):
    paths = delta(ctx.c0["admission_paths"], ctx.c1["admission_paths"])
    total = sum(paths.values())
    return 100.0 * paths.get("device", 0) / total if total else None

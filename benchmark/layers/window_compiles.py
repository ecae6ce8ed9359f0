"""Device programs: compile-ledger episodes (cold compiles and cache loads)
inside the window. Should be 0: every shape is warmed in set-up."""

from benchmark.counters import delta


def read(ctx):
    return float(sum(delta(ctx.c0["compile_episodes"], ctx.c1["compile_episodes"]).values()))

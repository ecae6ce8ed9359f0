"""Node process: span time on threads other than the driving thread and the
plane worker (the commit-notify workers' ``proof.build``, ``succinct.*``), as
the union of each thread's spans, per block: work that runs beside the next
block. Rule: ``benchmark/program_spans.py``."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.read(ctx, "background")

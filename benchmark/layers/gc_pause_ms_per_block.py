"""Node process: pauses of the cyclic collector (``gc.gen*`` records, measured
start to end by the node's ``gc.callbacks`` hook) on any thread inside the
window, per block. 0 where the hook is installed and no pause was recorded.
Rule: ``benchmark/program_spans.py``."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.read(ctx, "gc")

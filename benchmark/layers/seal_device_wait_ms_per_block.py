"""DevicePlane: time of a block's ``bench.seal_and_submit`` that the driving
thread spends waiting for the device: ``device.plane.wait`` (hash batches
through the plane, the coalescer's 2 ms window included) and
``device.<op>.sync`` (the merkle roots it dispatched itself).
Rule: ``benchmark/program_spans.py``."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.read(ctx, f"{ps.SEAL}|{ps.WAIT}")

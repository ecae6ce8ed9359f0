"""Crypto seam: ``device.admission*.sync``, the host waiting for the admission
program's result and bringing it over, every call of the window, per block.
Rule: ``benchmark/program_spans.py``."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.read(ctx, "sync")

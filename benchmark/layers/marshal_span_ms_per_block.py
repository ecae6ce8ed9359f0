"""Crypto seam: host time of ``device.admission*.marshal`` (keccak padding,
limb split, batch padding) and ``.unpack`` around the admission program,
every call of the window, per block. The inside view of what
``marshal_ms_per_block`` takes by subtraction in one traced block.
Rule: ``benchmark/program_spans.py``."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.read(ctx, "marshal")

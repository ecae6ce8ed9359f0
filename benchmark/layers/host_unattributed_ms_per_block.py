"""Sealer, PBFT, scheduler, storage: instants of a block's two ``bench.*``
spans that the split cannot give to a layer: under no program span at all,
or under one whose name maps to no group. How much of a block the spans
still miss. Rule: ``benchmark/program_spans.py``."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.read(ctx, *(f"{kind}|{g}" for kind in (ps.SUBMIT, ps.SEAL)
                          for g in (ps.NONE, ps.OTHER)))

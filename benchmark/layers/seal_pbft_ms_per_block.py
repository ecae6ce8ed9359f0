"""Sealer, PBFT, scheduler, storage: host time of a block's
``bench.seal_and_submit`` under ``seal``, ``pbft.*``, ``qc.*`` and
``txpool.verify_block`` and under none of the spans nested in them that have
a group of their own (execution, commit, waiting for the device).
Rule: ``benchmark/program_spans.py``."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.read(ctx, f"{ps.SEAL}|{ps.PBFT}")

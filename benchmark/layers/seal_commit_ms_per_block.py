"""Sealer, PBFT, scheduler, storage: host time of a block's
``bench.seal_and_submit`` under ``scheduler.commit_block`` and its children
(the 2PC legs), four replicas. Rule: ``benchmark/program_spans.py``."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.read(ctx, f"{ps.SEAL}|{ps.COMMIT}")

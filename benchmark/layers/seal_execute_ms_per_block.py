"""Sealer, PBFT, scheduler, storage: host time of a block's
``bench.seal_and_submit`` under ``scheduler.execute_block`` and its children
(``executor.execute``, ``dmc.execute``, the root dispatches), four replicas,
less the time they wait for the device. Rule: ``benchmark/program_spans.py``."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.read(ctx, f"{ps.SEAL}|{ps.EXECUTE}")

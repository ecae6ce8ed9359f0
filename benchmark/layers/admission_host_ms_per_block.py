"""TxPool admission + tx gossip: host time of a block's ``bench.submit_batch``
on the driving thread under the node's ``txpool.*`` and ``txsync.maintain``
spans and not waiting for the device (``device.plane.wait`` inside them is a
group of its own): static checks, decoding, pool inserts, gossip encoding.
Rule: ``benchmark/program_spans.py``."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.read(ctx, f"{ps.SUBMIT}|{ps.ADMISSION}")

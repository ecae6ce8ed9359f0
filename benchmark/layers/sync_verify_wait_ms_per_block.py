"""BlockSync: the part of the verification the replica spends blocked on the
plane: the phases of the window's admission calls (``fisco_device_phase_ms``
of the admission op: queue, marshal, enqueue, sync, unpack), per applied
block. What running the verification of one gather under the execution of
the one before could hide. Rule: ``benchmark/sync_counters.py``."""

from benchmark import sync_counters


def read(ctx):
    return sync_counters.per_block(ctx, "verify_wait_ms")

"""Crypto seam: what the host waits for the fused SM2/SM3 admission program, a call
(the window's sum of ``fisco_device_phase_ms{op="admission_sm",phase="sync"}``
over its delta of ``fisco_device_dispatch_path_total{op="admission",path="device"}``:
four calls a block, the entry node's and the three replicas'): the program's
run as the seam sees it, where ``device_sync_ms_per_block.flood`` is the same
wait a block. None where no batch took the device leg (a CPU rehearsal on the
native loop). Rule: ``benchmark/sm_counters.py``."""

from benchmark import sm_counters


def read(ctx):
    calls = sm_counters.window(ctx.cell, "admission", "calls_device")
    waited = sm_counters.window(ctx.cell, "admission_sm", "sync_ms")
    return waited / calls if calls and waited is not None else None

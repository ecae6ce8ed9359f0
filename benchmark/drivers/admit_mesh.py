"""Driver of the four-chip admission cell: ``admit``'s closed loop (one full
block of signatures after another through the public
``crypto.admission.admit_batch``, each call ending with the result on the
host; the same ``bench.admit_batch`` span, series keys, comparisons and
controls), on a node whose host holds the configuration's ``devices`` chips.
The cell sets nothing: the node's own rule (``admission.mesh_devices``) sends
a block of this bucket out over the local mesh. Where it does, every block of
the window has to have come back over a mesh of the configuration's size:
``blocks_not_over_the_mesh``, limit 0, from
``fisco_device_mesh_calls_total{op="admission",devices}``, which the program
adds to only once a mesh call's answer is on the host. A block that fell to
one chip, or that the host loop answered for under the breaker, is then a
wrong result and not a slow one. One more control reads the counter one call
short.

A checkout whose program has no such counter cannot show where its blocks
ran: it leaves at once with the harness's "no program" code, before any
corpus or compile."""

from __future__ import annotations

import sys

from benchmark import mesh_counters
from benchmark.drivers import admit


class Cell(admit.Cell):
    def setup(self, seconds: float) -> None:
        from fisco_bcos_tpu.observability import device as observatory

        if not hasattr(observatory.CompileLedger, "note_mesh_call"):
            print("benchmark: the program in this checkout does not count its mesh calls "
                  "(fisco_device_mesh_calls_total)", file=sys.stderr)
            raise SystemExit(3)  # run.RC_NO_PROGRAM
        import jax

        from fisco_bcos_tpu.crypto import admission
        from fisco_bcos_tpu.ops.hash_common import bucket_batch

        self.devices = int(self.config["devices"])
        bucket = bucket_batch(self.lanes_per_block)
        over = admission.mesh_devices(bucket)
        self.mesh_expected = over > 1
        print(f"mesh: {len(jax.devices())} local device(s), the configuration's {self.devices}; "
              f"the program's rule sends a bucket of {bucket} lanes over {over} "
              f"({bucket // over} lanes a device)", file=sys.stderr)
        super().setup(seconds)

    def window(self, seconds: float) -> None:
        self.mesh0 = mesh_counters.snapshot()
        super().window(seconds)
        self.mesh1 = mesh_counters.snapshot()
        per_block = {k: v / max(self.window_blocks, 1)
                     for k, v in mesh_counters.phase_ms(self.mesh0, self.mesh1).items()}
        print(f"mesh leg phases, ms per block: {per_block}", file=sys.stderr)

    def observe(self) -> dict:
        calls = mesh_counters.mesh_calls(self.mesh0, self.mesh1, "admission", self.devices)
        return dict(super().observe(), mesh_calls=calls)

    def compare(self, seen: dict) -> list[dict]:
        expected = self.window_blocks if self.mesh_expected else 0
        return super().compare(seen) + [
            {"name": "blocks_not_over_the_mesh",
             "value": int(expected - seen["mesh_calls"]), "limit": 0},
        ]

    def controls(self) -> dict:
        def one_call_short(seen):  # one block's answer did not come over the mesh
            seen["mesh_calls"] -= 1

        return dict(super().controls(), one_call_short=one_call_short)

"""Driver of the national-crypto admission cell: ``admit``'s closed loop (one
full block of signatures after another through the public
``crypto.admission.admit_batch``, each call ending with the result on the
host; the same ``bench.admit_batch`` span, series keys, comparisons and
controls), under the suite an ``sm_crypto=true`` chain holds: SM2 + SM3,
128-byte signatures r ‖ s ‖ pub. The extra block after the window has six
broken lanes (``generators/sm_signed_payloads.BROKEN``), and one more control
admits the lane that carries its neighbour's key.

A checkout whose program has no fused SM admission leaves at once with the
harness's "no program" code, before any corpus or compile."""

from __future__ import annotations

import sys
import time

from benchmark.drivers import admit
from benchmark.generators.sm_signed_payloads import Corpus


class Cell(admit.Cell):
    def setup(self, seconds: float) -> None:
        from fisco_bcos_tpu.crypto import admission
        from fisco_bcos_tpu.crypto.suite import sm_suite

        sm = sm_suite()
        if getattr(sm, "fused_admission", lambda: None)() is None:
            print("benchmark: the program in this checkout has no fused SM2/SM3 "
                  "admission (CryptoSuite.fused_admission)", file=sys.stderr)
            raise SystemExit(3)  # run.RC_NO_PROGRAM
        self._admit = lambda payloads, sigs: admission.admit_batch(payloads, sigs, suite=sm)
        t = time.monotonic()
        self.corpus = Corpus(self.traffic, self.seed)
        self.setup_parts["corpus_s"] = time.monotonic() - t
        t = time.monotonic()
        self._block(0, keep=False)  # traces, loads or compiles the one shape
        self.setup_parts["admission_program_s"] = time.monotonic() - t
        t = time.monotonic()
        self._block(1, keep=False)
        self.setup_parts["warm_batches_s"] = time.monotonic() - t

    def controls(self) -> dict:
        def accepted_neighbours_key(seen):  # the lane carrying the next signer's key admitted
            seen["corrupt"]["ok"][self.corrupt_lanes[5]] = True

        return dict(super().controls(), accepted_neighbours_key=accepted_neighbours_key)

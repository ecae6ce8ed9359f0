"""Wall-clock sanity check for the TPU EC throughput numbers.

Timed-repetition probes can show numbers good enough to distrust. This
feeds K DISTINCT batches (fresh host data every call, so no conceivable
caching can help), validates every
output against known-good pubkeys, and reports end-to-end wall time
including host->device transfer of each batch.

Usage: python -m tool.tpu_sanity [batch] [calls]
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(batch: int = 10240, calls: int = 20) -> int:
    import jax

    from fisco_bcos_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()
    from fisco_bcos_tpu.crypto import suite as cs
    from fisco_bcos_tpu.ops import secp256k1 as k1
    from fisco_bcos_tpu.ops.bigint import bytes_be_to_limbs

    rng = np.random.default_rng(11)
    sec = cs.Secp256k1Crypto()
    kps = [sec.generate_keypair(int(rng.integers(1, 2**62))) for _ in range(4)]
    pubs_by_kp = [np.frombuffer(kp.pub, dtype=np.uint8) for kp in kps]

    # sign 'batch' base messages once (host), then derive per-call variants:
    # each call re-signs a rotated slice... too slow on host. Instead:
    # pre-sign `calls` distinct batches of a smaller unique core and tile.
    core = 512
    print(f"signing {calls} x {core} core messages (native host path) ...", flush=True)
    batches = []
    for c in range(calls):
        msgs = [rng.integers(0, 256, 32, dtype=np.uint8).tobytes() for _ in range(core)]
        sigs = [sec.sign(kps[i % 4], m) for i, m in enumerate(msgs)]
        z = np.stack([np.frombuffer(m, dtype=np.uint8) for m in msgs])
        r = np.stack([np.frombuffer(s[:32], dtype=np.uint8) for s in sigs])
        s_ = np.stack([np.frombuffer(s[32:64], dtype=np.uint8) for s in sigs])
        v = np.array([s[64] for s in sigs], dtype=np.int32)
        k = batch // core
        exp_pub = np.stack([pubs_by_kp[i % 4] for i in range(core)])
        batches.append(
            (
                np.tile(z, (k, 1)),
                np.tile(r, (k, 1)),
                np.tile(s_, (k, 1)),
                np.tile(v, k),
                np.tile(exp_pub, (k, 1)),
            )
        )

    # warmup/compile on batch 0
    z, r, s_, v, exp = batches[0]
    out = k1._recover_xla(
        bytes_be_to_limbs(z), bytes_be_to_limbs(r), bytes_be_to_limbs(s_), v
    )
    jax.block_until_ready(out)
    print("compiled; measuring ...", flush=True)

    t0 = time.perf_counter()
    oks = 0
    results = []
    for z, r, s_, v, exp in batches:
        qx, qy, ok = k1._recover_xla(
            bytes_be_to_limbs(z), bytes_be_to_limbs(r), bytes_be_to_limbs(s_), v
        )
        results.append((qx, qy, ok))
    for qx, qy, ok in results:
        oks += int(np.asarray(ok).sum())
    wall = time.perf_counter() - t0
    total = batch * calls
    print(
        f"recover wall: {wall:.3f}s for {calls} x {batch} = {total} recovers "
        f"-> {total/wall:,.0f}/s end-to-end (incl. H2D per call); ok {oks}/{total}"
    )

    # correctness on the last batch: recovered pubkeys must equal signers'
    from fisco_bcos_tpu.ops.bigint import limbs_to_bytes_be

    qb = np.concatenate(
        [limbs_to_bytes_be(np.asarray(qx)), limbs_to_bytes_be(np.asarray(qy))], axis=1
    )
    match = (qb == exp).all(axis=1).sum()
    print(f"pubkey match on last batch: {match}/{batch}")
    return 0 if oks == total and match == batch else 1


if __name__ == "__main__":
    b = int(sys.argv[1]) if len(sys.argv) > 1 else 10240
    c = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    sys.exit(main(b, c))

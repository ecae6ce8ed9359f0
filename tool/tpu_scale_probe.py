"""Batch-size scaling + component profile for the EC XLA paths on TPU.

Breaks recover into its stages (inv, sqrt leg, ladder, finish) and times
verify/recover/sm2 at growing batch sizes, warm, to find where the VPU
saturates and which stage recover loses its time in.

Usage: python -m tool.tpu_scale_probe
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_T0 = time.monotonic()


def _log(msg: str) -> None:
    print(f"[{time.monotonic() - _T0:8.1f}s] {msg}", flush=True)


def _time(fn, *args, reps=10):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / reps


def main() -> int:
    import jax
    import jax.numpy as jnp

    from fisco_bcos_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()
    _log(f"backend={jax.default_backend()}")
    from fisco_bcos_tpu.crypto import suite as cs
    from fisco_bcos_tpu.ops import secp256k1 as k1
    from fisco_bcos_tpu.ops.bigint import bytes_be_to_limbs

    rng = np.random.default_rng(7)
    sec = cs.Secp256k1Crypto()
    kps = [sec.generate_keypair(int(rng.integers(1, 2**62))) for _ in range(8)]
    base = 256
    msgs = [rng.integers(0, 256, 32, dtype=np.uint8).tobytes() for _ in range(base)]
    sigs = [sec.sign(kps[i % 8], m) for i, m in enumerate(msgs)]
    pubs = [kps[i % 8].pub for i in range(base)]
    z0 = np.stack([np.frombuffer(m, dtype=np.uint8) for m in msgs])
    r0 = np.stack([np.frombuffer(s[:32], dtype=np.uint8) for s in sigs])
    s0 = np.stack([np.frombuffer(s[32:64], dtype=np.uint8) for s in sigs])
    v0 = np.array([s[64] for s in sigs], dtype=np.int32)
    p0 = np.stack([np.frombuffer(p, dtype=np.uint8) for p in pubs])

    def tile_to(b):
        k = b // base
        return (
            bytes_be_to_limbs(np.tile(z0, (k, 1))),
            bytes_be_to_limbs(np.tile(r0, (k, 1))),
            bytes_be_to_limbs(np.tile(s0, (k, 1))),
            np.tile(v0, k),
            bytes_be_to_limbs(np.tile(p0[:, :32], (k, 1))),
            bytes_be_to_limbs(np.tile(p0[:, 32:], (k, 1))),
        )

    # ---- scaling ----
    for b in (256, 2048, 10240):
        zl, rl, sl, v, qxl, qyl = tile_to(b)
        _, tv = _time(k1._verify_xla, zl, rl, sl, qxl, qyl)
        _log(f"B={b:6d} verify  {tv*1e3:9.2f} ms  ({b/tv:12.0f}/s)")
        _, tr = _time(k1._recover_xla, zl, rl, sl, v)
        _log(f"B={b:6d} recover {tr*1e3:9.2f} ms  ({b/tr:12.0f}/s)")

    # ---- recover component profile at 2048 ----
    b = 2048
    zl, rl, sl, v, qxl, qyl = tile_to(b)
    from fisco_bcos_tpu.ops.secp256k1 import (
        _g_table,
        inv_mod_n,
        recover_finish,
        recover_project_core,
    )

    gt = _g_table()

    @jax.jit
    def stage_inv(r):
        return inv_mod_n(r.T)

    @jax.jit
    def stage_project(z, r, s, v, rinv):
        return recover_project_core(z.T, r.T, s.T, v, rinv, gt)

    @jax.jit
    def stage_finish(X, Y, Z, ok):
        return recover_finish(X, Y, Z, ok)

    rinv, t1 = _time(stage_inv, rl)
    (X, Y, Z, ok), t2 = _time(stage_project, zl, rl, sl, v, rinv)
    _, t3 = _time(stage_finish, X, Y, Z, ok)
    _log(f"recover stages @2048: inv {t1*1e3:.2f} ms, project {t2*1e3:.2f} ms, finish {t3*1e3:.2f} ms")

    # ---- sm2 scaling ----
    from fisco_bcos_tpu.ops import sm2 as sm2ops

    sm2 = cs.SM2Crypto()
    kp2 = [sm2.generate_keypair(int(rng.integers(1, 2**62))) for _ in range(8)]
    sig2 = [sm2.sign(kp2[i % 8], m) for i, m in enumerate(msgs)]
    pub2 = np.stack([np.frombuffer(kp2[i % 8].pub[:64], dtype=np.uint8) for i in range(base)])
    e0 = sm2ops.sm2_e_batch(z0, pub2)
    r20 = np.stack([np.frombuffer(s[:32], dtype=np.uint8) for s in sig2])
    s20 = np.stack([np.frombuffer(s[32:64], dtype=np.uint8) for s in sig2])
    for b in (2048, 10240):
        k = b // base
        el = bytes_be_to_limbs(np.tile(e0, (k, 1)))
        r2l = bytes_be_to_limbs(np.tile(r20, (k, 1)))
        s2l = bytes_be_to_limbs(np.tile(s20, (k, 1)))
        qx2l = bytes_be_to_limbs(np.tile(pub2[:, :32], (k, 1)))
        qy2l = bytes_be_to_limbs(np.tile(pub2[:, 32:], (k, 1)))
        out, t = _time(sm2ops._verify_xla, el, r2l, s2l, qx2l, qy2l)
        ok_n = int(np.asarray(out).sum())
        _log(f"B={b:6d} sm2_verify {t*1e3:9.2f} ms  ({b/t:12.0f}/s)  valid {ok_n}/{b}")

    _log("SCALE PROBE DONE")
    return 0


if __name__ == "__main__":
    sys.exit(main())

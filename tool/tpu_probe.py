"""First-hardware-compile probe for the Pallas EC kernels.

Runs each Pallas kernel DIRECTLY (no pallas_or_xla degrade latch, so a
Mosaic failure surfaces as a traceback), checks bit-identity against the
XLA path on the same inputs, and times both steady-state. Run it on the
chip to qualify kernels the CPU interpreter can't: Mosaic rejects
constructs interpret-mode accepts.

Usage: python -m tool.tpu_probe [batch]
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


def _time(fn, *args, reps=5):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / reps


def main(batch: int = 1024) -> int:
    import jax

    from fisco_bcos_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()
    _log(f"backend={jax.default_backend()} devices={jax.devices()}")
    from fisco_bcos_tpu.crypto import suite as cs
    from fisco_bcos_tpu.ops import secp256k1 as k1
    from fisco_bcos_tpu.ops.bigint import bytes_be_to_limbs

    rng = np.random.default_rng(7)
    failures = []

    # --- build a real secp256k1 batch (sign on host, one bad lane) ---
    sec = cs.Secp256k1Crypto()
    kps = [sec.generate_keypair(int(rng.integers(1, 2**62))) for _ in range(8)]
    msgs = [rng.integers(0, 256, 32, dtype=np.uint8).tobytes() for _ in range(batch)]
    sigs, pubs = [], []
    for i, m in enumerate(msgs):
        kp = kps[i % len(kps)]
        sigs.append(sec.sign(kp, m))
        pubs.append(kp.pub)
    z = np.stack([np.frombuffer(m, dtype=np.uint8) for m in msgs])
    r = np.stack([np.frombuffer(s[:32], dtype=np.uint8) for s in sigs])
    s_ = np.stack([np.frombuffer(s[32:64], dtype=np.uint8) for s in sigs])
    v = np.array([s[64] for s in sigs], dtype=np.int32)
    pub = np.stack([np.frombuffer(p, dtype=np.uint8) for p in pubs])
    r[0] ^= 0xFF  # one corrupted lane must read invalid on every path

    zl = bytes_be_to_limbs(z)
    rl = bytes_be_to_limbs(r)
    sl = bytes_be_to_limbs(s_)
    qxl = bytes_be_to_limbs(pub[:, :32])
    qyl = bytes_be_to_limbs(pub[:, 32:])

    from fisco_bcos_tpu.ops import pallas_ec as pe

    for name, fnp, fnx, args in (
        ("secp_verify", pe.verify_pallas, k1._verify_xla, (zl, rl, sl, qxl, qyl)),
        ("secp_recover", pe.recover_pallas, k1._recover_xla, (zl, rl, sl, v)),
    ):
        _log(f"{name}: compiling+running pallas ...")
        try:
            outp, tp = _time(fnp, *args)
        except Exception as e:
            failures.append(name)
            _log(f"[FAIL] {name} pallas: {type(e).__name__}: {str(e)[:400]}")
            continue
        _log(f"{name}: pallas done; compiling+running xla ...")
        outx, tx = _time(fnx, *args)
        same = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(outp), jax.tree.leaves(outx))
        )
        okvec = np.asarray(jax.tree.leaves(outp)[-1])
        print(
            f"[{'ok' if same else 'MISMATCH'}] {name}: pallas {tp*1e3:.2f} ms, "
            f"xla {tx*1e3:.2f} ms ({tx/tp:.2f}x), valid {int(okvec.sum())}/{batch}"
        )
        if not same:
            failures.append(name)

    # --- SM2 ---
    from fisco_bcos_tpu.ops import sm2 as sm2ops

    sm2 = cs.SM2Crypto()
    kp2 = [sm2.generate_keypair(int(rng.integers(1, 2**62))) for _ in range(8)]
    r2, s2, pub2 = [], [], []
    for i, m in enumerate(msgs):
        kp = kp2[i % len(kp2)]
        sig = sm2.sign(kp, m)
        r2.append(np.frombuffer(sig[:32], dtype=np.uint8))
        s2.append(np.frombuffer(sig[32:64], dtype=np.uint8))
        pub2.append(np.frombuffer(kp.pub[:64], dtype=np.uint8))
    pub2 = np.stack(pub2)
    e2 = sm2ops.sm2_e_batch(z, pub2)
    el = bytes_be_to_limbs(e2)
    r2l = bytes_be_to_limbs(np.stack(r2))
    s2l = bytes_be_to_limbs(np.stack(s2))
    qx2l = bytes_be_to_limbs(pub2[:, :32])
    qy2l = bytes_be_to_limbs(pub2[:, 32:])
    _log("sm2_verify: compiling+running pallas ...")
    try:
        outp, tp = _time(pe.sm2_verify_pallas, el, r2l, s2l, qx2l, qy2l)
    except Exception as e:
        failures.append("sm2_verify")
        print(f"[FAIL] sm2_verify pallas: {type(e).__name__}: {str(e)[:400]}")
    else:
        _log("sm2_verify: pallas done; compiling+running xla ...")
        outx, tx = _time(sm2ops._verify_xla, el, r2l, s2l, qx2l, qy2l)
        same = np.array_equal(np.asarray(outp), np.asarray(outx))
        print(
            f"[{'ok' if same else 'MISMATCH'}] sm2_verify: pallas {tp*1e3:.2f} ms, "
            f"xla {tx*1e3:.2f} ms ({tx/tp:.2f}x), valid {int(np.asarray(outp).sum())}/{batch}"
        )
        if not same:
            failures.append("sm2_verify")

    _log("PROBE " + ("FAIL " + ",".join(failures) if failures else "ALL OK"))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]) if len(sys.argv) > 1 else 1024))

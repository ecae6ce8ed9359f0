#!/usr/bin/env python
"""Pre-warm the persistent XLA compile cache for every jitted program the
node can dispatch — the ISSUE 13 operational answer to hour-class cold
compiles (the BLS pairing program costs ~54 min on XLA-CPU; a node taking
traffic before `.jax_cache` holds it parks a consensus lane inside the
compiler).

Walks the SAME jit inventory the static analyzers use
(``python -m fisco_bcos_tpu.analysis --list-jit``): every inventoried
program is either warmed — its host wrapper is driven with shape-bucketed
dummy inputs, compiling it into the persistent cache
(``fisco_bcos_tpu.utils.jaxenv.configure_compile_cache``) — or listed
as skipped with a reason (sharded variants on a
single-device host, BLS on CPU backends where the crypto seam routes to
the host reference anyway; ``--include-bls`` forces it). The compile
ledger (observability/device.py) measures every program: the manifest
records per program whether the cache served it (``persistent_cache``) or
a true cold compile ran, with the measured walls.

Contract: a FIRST run on an empty cache reports cold compiles; a SECOND
run must report **zero** cold compiles (``--expect-warm`` turns that into
the exit code, for boot scripts and CI).

Usage::

    python tool/warm_cache.py [--bucket N] [--ops a,b,...] [--include-bls]
        [--out warm_cache.manifest.json] [--expect-warm] [--list]

Dummy inputs are garbage by design: the kernels' contract is that invalid
rows lower validity-lane bits, never raise — compilation only depends on
shapes. Run with the SAME XLA flags/backend the node will use: the
persistent-cache key covers compile options, so a cache warmed under
different flags does not serve the production process.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


# ---------------------------------------------------------------------------
# Warmers: inventory file -> how to compile its programs (or why not to)
# ---------------------------------------------------------------------------


def _warm_keccak(bucket: int) -> None:
    from fisco_bcos_tpu.ops import keccak as k

    k.keccak256_batch([b"warm-cache %d" % i for i in range(bucket)])


def _warm_sha256(bucket: int) -> None:
    from fisco_bcos_tpu.ops import sha256 as s

    s.sha256_batch([b"warm-cache %d" % i for i in range(bucket)])


def _warm_sm3(bucket: int) -> None:
    from fisco_bcos_tpu.ops import sm3 as s

    s.sm3_batch([b"warm-cache %d" % i for i in range(bucket)])


def _warm_secp256k1(bucket: int) -> None:
    import numpy as np

    from fisco_bcos_tpu.ops import secp256k1 as secp

    z = np.ones((bucket, 32), np.uint8)
    secp.verify_batch(z, z, z, np.ones((bucket, 64), np.uint8))
    secp.recover_batch(z, np.ones((bucket, 65), np.uint8))


def _warm_sm2(bucket: int) -> None:
    import numpy as np

    from fisco_bcos_tpu.ops import sm2

    z = np.ones((bucket, 32), np.uint8)
    sm2.verify_batch(z, z, z, np.ones((bucket, 64), np.uint8))


def _warm_ed25519(bucket: int) -> None:
    from fisco_bcos_tpu.ops import ed25519 as ed

    msgs = [b"warm-cache %d" % i for i in range(bucket)]
    ed.verify_batch(msgs, [b"\x01" * 32] * bucket, [b"\x02" * 64] * bucket)


def _warm_address(bucket: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from fisco_bcos_tpu.observability.device import device_span
    from fisco_bcos_tpu.ops.address import sender_address_device
    from fisco_bcos_tpu.ops.hash_common import bucket_batch

    bb = bucket_batch(max(bucket, 1))
    q = jnp.asarray(np.ones((bb, 16), np.uint32))
    # no host wrapper of its own (admission's fused program subsumes it in
    # production), so the warmer attributes the ledger entry itself
    with device_span("sender_address", bb, shape_key=bb):
        np.asarray(sender_address_device(q, q))


def _warm_admission(bucket: int) -> None:
    import numpy as np

    from fisco_bcos_tpu.crypto.admission import _BODIES, _admit_batch_device

    payloads = [b"warm-cache admission %d" % i for i in range(bucket)]
    for body in _BODIES.values():  # secp256k1 + keccak256, SM2 + SM3
        _admit_batch_device(
            payloads, np.ones((bucket, body.sig_len), np.uint8), body=body
        )


def _warm_merkle(bucket: int):
    import numpy as np

    from fisco_bcos_tpu.ops import merkle

    if merkle._prefer_host_tree():
        return "host-tree policy on this backend (device tree never compiles)"
    leaves = np.ones((max(bucket, 256), 32), np.uint8)
    # keccak's root program, and the SM3 tree an sm_crypto node's roots and
    # proof trees share
    for hasher in merkle._FUSED_ROOT:
        merkle.merkle_root(leaves, hasher=hasher)
    return None


def _warm_bls(bucket: int) -> None:
    from fisco_bcos_tpu.crypto.ref import bls12_381 as ref
    from fisco_bcos_tpu.ops import bls12_381 as bls

    hm = ref.ec_mul(ref.G2, 2, ref.FP2_OPS)
    bls.pairing_check_batch([(ref.G1, ref.G2, hm)] * max(bucket, 1))
    # the succinct-sync multi-pairing program (ISSUE 18): same Miller-loop
    # core, different fan-in shape — pairs bucket to the next power of two
    bls.multi_pairing_check([(ref.G1, ref.G2), (ref.G1, hm)])


def _warm_poseidon(bucket: int) -> None:
    from fisco_bcos_tpu.ops import poseidon as pos

    pos.poseidon_batch([b"warm-cache %d" % i for i in range(max(bucket, 1))])


def _skip_sharded(_bucket: int):
    import jax

    ndev = len(jax.devices())
    if ndev <= 1:
        return "single-device host (no mesh; sharded variants never trace)"
    return (
        f"{ndev}-device mesh present but sharded programs warm on first "
        "dispatch (shapes depend on the deployment's fan-out threshold)"
    )


# file (as jitmap.inventory reports it) -> (op label, warmer).  A warmer
# returns None (warmed) or a skip-reason string; raising marks it failed.
WARMERS = {
    "fisco_bcos_tpu/ops/keccak.py": ("keccak256", _warm_keccak),
    "fisco_bcos_tpu/ops/sha256.py": ("sha256", _warm_sha256),
    "fisco_bcos_tpu/ops/sm3.py": ("sm3", _warm_sm3),
    "fisco_bcos_tpu/ops/secp256k1.py": ("secp256k1", _warm_secp256k1),
    "fisco_bcos_tpu/ops/sm2.py": ("sm2", _warm_sm2),
    "fisco_bcos_tpu/ops/ed25519.py": ("ed25519", _warm_ed25519),
    "fisco_bcos_tpu/ops/address.py": ("address", _warm_address),
    "fisco_bcos_tpu/ops/merkle.py": ("merkle", _warm_merkle),
    "fisco_bcos_tpu/ops/bls12_381.py": ("bls12_381", _warm_bls),
    "fisco_bcos_tpu/ops/poseidon.py": ("poseidon", _warm_poseidon),
    "fisco_bcos_tpu/parallel/sharding.py": ("sharding", _skip_sharded),
    "fisco_bcos_tpu/crypto/admission.py": ("admission", _warm_admission),
}


def run_warm(
    ops: list[str] | None = None,
    bucket: int = 256,
    include_bls: bool = False,
    out: str | None = None,
) -> dict:
    """Drive the warmers over the jit inventory; returns (and optionally
    writes) the manifest. Importable — tests and boot scripts call this
    directly."""
    from fisco_bcos_tpu.analysis import jitmap
    from fisco_bcos_tpu.observability.device import (
        LEDGER,
        install_jax_hooks,
    )
    from fisco_bcos_tpu.utils.jaxenv import (
        configure_compile_cache,
        device_backend_is_cpu,
        device_identity,
    )

    cache_dir = configure_compile_cache()
    backend = device_identity()["platform"]
    hooks = install_jax_hooks()
    LEDGER.reset()
    inventory = jitmap.inventory()
    by_file: dict[str, list[dict]] = {}
    for prog in inventory:
        by_file.setdefault(prog["file"], []).append(prog)

    warmed: list[str] = []
    skipped: list[dict] = []
    failed: list[dict] = []
    t_start = time.perf_counter()
    for file, progs in sorted(by_file.items()):
        entry = WARMERS.get(file)
        if entry is None:
            skipped.append(
                {"op": file, "reason": "no warmer registered — ADD ONE "
                 "(the pinned inventory test should have caught this)"}
            )
            continue
        op, warmer = entry
        if ops is not None and op not in ops:
            skipped.append({"op": op, "reason": "filtered by --ops"})
            continue
        if op == "bls12_381" and not include_bls and device_backend_is_cpu():
            skipped.append(
                {"op": op, "reason": "CPU backend routes BLS to the host "
                 "reference (hour-class XLA-CPU compile; --include-bls "
                 "forces it)"}
            )
            continue
        t0 = time.perf_counter()
        try:
            reason = warmer(bucket)
        except Exception as e:  # keep warming the rest; manifest names it
            failed.append({"op": op, "error": f"{type(e).__name__}: {e}"})
            continue
        if reason is not None:
            skipped.append({"op": op, "reason": reason})
        else:
            warmed.append(op)
            print(
                f"# warmed {op} ({len(progs)} inventoried program(s)) in "
                f"{time.perf_counter() - t0:.1f}s",
                flush=True,
            )

    rows = LEDGER.snapshot()
    manifest = {
        "ts": time.time(),
        "backend": backend,
        "cache_dir": cache_dir,
        "bucket": bucket,
        "jax_hooks": hooks,
        "wall_s": round(time.perf_counter() - t_start, 3),
        "inventory_programs": len(inventory),
        "warmed": warmed,
        "skipped": skipped,
        "failed": failed,
        "programs": rows,
        "cold_compiles": sum(r["cold_compiles"] for r in rows),
        "cache_hits": sum(r["cache_hits"] for r in rows),
    }
    if out:
        with open(out, "w") as f:
            json.dump(manifest, f, indent=1, default=str)
        print(f"# manifest -> {out}", flush=True)
    return manifest


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--bucket", type=int,
        default=int(os.environ.get("FISCO_TEST_BUCKET", "") or 256),
        help="batch bucket to compile for (default 256, or "
        "FISCO_TEST_BUCKET when set)",
    )
    ap.add_argument(
        "--ops", default=None,
        help="comma-separated warmer subset (see --list)",
    )
    ap.add_argument(
        "--include-bls", action="store_true",
        help="compile the BLS pairing program even on CPU backends "
        "(hour-class on XLA-CPU — budget accordingly)",
    )
    ap.add_argument("--out", default="warm_cache.manifest.json")
    ap.add_argument(
        "--expect-warm", action="store_true",
        help="exit 1 when any cold compile ran (the second-run gate)",
    )
    ap.add_argument(
        "--list", action="store_true",
        help="print the registered warmers and exit",
    )
    args = ap.parse_args(argv)
    if args.list:
        for file, (op, _fn) in sorted(WARMERS.items()):
            print(f"{op:<12} {file}")
        return 0
    ops = [o for o in (args.ops or "").split(",") if o] or None
    if ops:
        known = {op for op, _fn in WARMERS.values()}
        unknown = sorted(set(ops) - known)
        if unknown:
            # a typo must not silently skip every warmer and let
            # --expect-warm pass vacuously on a cold cache
            print(
                f"unknown --ops name(s) {unknown}; known: {sorted(known)}"
            )
            return 2
    manifest = run_warm(
        ops=ops, bucket=args.bucket, include_bls=args.include_bls,
        out=args.out,
    )
    print(
        f"warm-cache: {len(manifest['warmed'])} warmer(s) run, "
        f"{manifest['cold_compiles']} cold compile(s), "
        f"{manifest['cache_hits']} persistent-cache load(s), "
        f"{len(manifest['skipped'])} skipped, "
        f"{len(manifest['failed'])} failed "
        f"({manifest['wall_s']}s, backend={manifest['backend']})"
    )
    if manifest["failed"]:
        return 1
    if args.expect_warm and manifest["cold_compiles"] > 0:
        print("FAIL: cache was expected warm but cold compiles ran")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

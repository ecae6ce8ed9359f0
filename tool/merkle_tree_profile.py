#!/usr/bin/env python3
"""Device time of an SM3 merkle tree as one program, beside the level-by-level
path it replaced and keccak's fused root.

Builds ``ops/merkle._device_tree_fn("sm3", bucket, width)`` at each asked
unroll of the round scan (``ops/merkle._SM3_ROUND_UNROLL``: the one number the
chip chose, PERF.md §6, PR 45) and times it three ways: ``--calls`` calls
queued back to back and waited for once (the device's time a call, where the
host enqueues faster than the device runs), one call and its transfer at a time
(what a proof tree's builder waits), and the program's own event on the
device's ``XLA Modules`` line under ``jax.profiler``. The same leaves then go
through ``MerkleTree`` with the fused route off (a hash batch and a sync a
level) and through keccak's ``_device_root_fn``. Every tree is checked against
the host's before it is timed.

    python tool/merkle_tree_profile.py [--leaves 1000] [--width 16] [--unroll 1,4,8,16]

Refuses to run off the chip (exit 4): a loop step of the CPU backend costs
nothing like the TPU's. Writes ``chiprun_out/merkle_tree_profile/<label>.json``
and prints the same JSON as its last line.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _module_ms(call, calls: int) -> dict:
    """``calls`` calls under the profiler -> {module name: mean ms an event}."""
    import jax

    from tool.admission_op_profile import reduce_ops

    trace_dir = tempfile.mkdtemp(prefix="merkle_tree_profile_")
    jax.profiler.start_trace(trace_dir)
    try:
        for _ in range(calls):
            call()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    by_name: dict[str, list[float]] = {}
    for ev in reduce_ops(path).get("modules", []):
        by_name.setdefault(ev["name"].split("(")[0], []).append(ev["ms"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    return {
        name: {"events": len(ms), "mean_ms": statistics.fmean(ms), "sum_ms": sum(ms)}
        for name, ms in by_name.items()
    }


def time_program(fn, arr, calls: int) -> dict:
    """A jitted program on a device-resident operand, the three ways."""
    import numpy as np

    fn(arr).block_until_ready()
    t = time.perf_counter()
    for _ in range(calls):
        out = fn(arr)
    out.block_until_ready()
    queued_ms = (time.perf_counter() - t) * 1e3 / calls
    alone = []
    for _ in range(50):
        t = time.perf_counter()
        np.asarray(fn(arr))
        alone.append((time.perf_counter() - t) * 1e3)
    return {
        "queued_ms_a_call": queued_ms,
        "call_and_transfer_ms_median": statistics.median(alone),
        "modules": _module_ms(lambda: np.asarray(fn(arr)), 20),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--leaves", type=int, default=1000)
    ap.add_argument("--width", type=int, default=16)
    ap.add_argument("--unroll", default="1,4,8,16")
    ap.add_argument("--calls", type=int, default=200)
    ap.add_argument("--seed", type=int, default=2150450001)
    ap.add_argument("--label", default="tree")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from fisco_bcos_tpu.ops import merkle

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"merkle_tree_profile: on {dev.platform}, not a TPU: nothing to time here")
        return 4
    n, width = args.leaves, args.width
    b = merkle.bucket_leaves(n)
    leaves = np.random.default_rng(args.seed).integers(0, 256, (n, 32), dtype=np.uint8)
    padded = np.vstack([leaves, np.zeros((b - n, 32), np.uint8)])
    arr = jax.device_put(jnp.asarray(padded))
    host = {
        h: merkle._levels(padded, width, merkle._host_hash_batch(h))
        for h in ("sm3", "keccak256")
    }
    result = {
        "label": args.label,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()},
        "leaves": n, "bucket": b, "width": width, "calls": args.calls, "fused": {},
    }
    chosen = merkle._SM3_ROUND_UNROLL
    for unroll in [int(u) for u in args.unroll.split(",")]:
        merkle._SM3_ROUND_UNROLL = unroll
        merkle._device_tree_fn.cache_clear()
        fn = merkle._device_tree_fn("sm3", b, width)
        t = time.perf_counter()
        rows = np.split(np.asarray(fn(arr)), merkle._level_offsets(b, width))
        first_s = time.perf_counter() - t  # the compile, or its load from the cache
        assert all(np.array_equal(x, y) for x, y in zip(rows, host["sm3"][1:], strict=True))
        result["fused"][f"unroll_{unroll}"] = {
            "first_call_s": first_s, **time_program(fn, arr, args.calls)}
        print(f"[merkle tree] unroll {unroll}: {result['fused'][f'unroll_{unroll}']}",
              file=sys.stderr, flush=True)
    merkle._SM3_ROUND_UNROLL = chosen
    merkle._device_tree_fn.cache_clear()

    # the path an SM3 tree took before: a hash batch and a sync a level
    fused_tree, merkle._FUSED_TREE = merkle._FUSED_TREE, ()
    try:
        tree = merkle.MerkleTree(leaves, width=width, hasher="sm3")
        assert not tree.fused and tree.padded_root == bytes(host["sm3"][-1][0])
        walls = []
        for _ in range(50):
            t = time.perf_counter()
            merkle.MerkleTree(leaves, width=width, hasher="sm3")
            walls.append((time.perf_counter() - t) * 1e3)
        modules = _module_ms(lambda: merkle.MerkleTree(leaves, width=width, hasher="sm3"), 20)
    finally:
        merkle._FUSED_TREE = fused_tree
    result["levels"] = {
        "tree_ms_median": statistics.median(walls),
        "modules_a_tree": {
            name: {"events": m["events"] / 20, "sum_ms": m["sum_ms"] / 20}
            for name, m in modules.items()
        },
    }

    root = merkle._device_root_fn(b, width)
    assert bytes(np.asarray(root(arr))) == bytes(host["keccak256"][-1][0])
    result["keccak_root"] = time_program(root, arr, args.calls)

    out_dir = os.path.join(ROOT, "chiprun_out", "merkle_tree_profile")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{args.label}.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

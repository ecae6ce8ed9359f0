#!/usr/bin/env python3
"""Device-op profile of the fused admission program, one call a shape.

Runs ``crypto.admission._admit_batch_device`` (the program every benchmark
cell waits for) at 1,000 lanes (bucket 1,024) and 10,000 lanes (bucket
10,240) under ``jax.profiler`` with the device's ops line on, and reduces
the capture to: device ops a call, the op kinds by count and by time, the
program's device time. The mechanism counter of PERF.md §6 (PR 25).

    python tool/admission_op_profile.py --label parent [--lanes 1000,10000]

Refuses to run off the chip (exit 4): an op count of the CPU backend says
nothing about the TPU's fusions. Writes ``chiprun_out/op_profile/<label>.json``
and prints the same JSON as its last line.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _kind(name: str) -> tuple[str, str]:
    """An op event's name is the instruction's HLO text, ``and_or_fusion.71 =
    (s32[17,1024]{...}, ...) fusion(...), kind=kLoop, ...`` -> (the
    instruction's name without its number: ``and_or_fusion``, its opcode:
    ``fusion``)."""
    head, _, rest = name.partition(" = ")
    head = re.sub(r"[.\d]+$", "", head.lstrip("%")) or head
    m = re.search(r" ([a-z][a-z\-]*)\(", " " + rest)
    return head, (m.group(1) if m else head)


def reduce_ops(path: str) -> dict:
    """One capture -> the program's device time (``modules``), device ops a
    call (``ops``), and the op kinds by opcode, by count and by time."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            events = list(line.events)
            if line.name == "XLA Modules":
                out["modules"] = [
                    {"name": e.name, "ms": e.duration_ns / 1e6} for e in events
                ]
            elif line.name == "XLA Ops":
                count = collections.Counter()
                ns = collections.Counter()
                op_count = collections.Counter()
                op_ns = collections.Counter()
                for e in events:
                    k, opcode = _kind(e.name)
                    count[k] += 1
                    ns[k] += e.duration_ns
                    op_count[opcode] += 1
                    op_ns[opcode] += e.duration_ns
                out["ops"] = len(events)
                # a `while` event spans its body's events: the sum counts those twice
                out["ops_time_sum_ms"] = sum(ns.values()) / 1e6
                out["opcodes"] = [
                    {"opcode": k, "n": n, "ms": round(op_ns[k] / 1e6, 3)}
                    for k, n in op_count.most_common(10)
                ]
                out["by_count"] = [
                    {"kind": k, "n": n, "ms": round(ns[k] / 1e6, 3)} for k, n in count.most_common(10)
                ]
                out["by_time"] = [
                    {"kind": k, "n": count[k], "ms": round(v / 1e6, 3)} for k, v in ns.most_common(10)
                ]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--label", required=True)
    ap.add_argument("--lanes", default="1000,10000")
    ap.add_argument("--seed", type=int, default=2500000001)
    args = ap.parse_args()

    from fisco_bcos_tpu.utils import jaxenv

    jaxenv.configure_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"refused: op profile needs the chip, got {dev.platform}", file=sys.stderr)
        return 4

    from benchmark.generators.signed_payloads import Corpus
    from fisco_bcos_tpu.crypto import admission

    lanes = [int(x) for x in args.lanes.split(",")]
    blocks = {
        n: Corpus({"lanes": n, "signers": 64, "rotations": 1}, args.seed).blocks[0]
        for n in lanes
    }

    def call(n):
        b = blocks[n]
        return admission._admit_batch_device(b["payloads"], b["sigs"])

    # first call of a shape traces and compiles (or loads): the shapes side by side
    setup = {}

    def warm(n):
        t = time.monotonic()
        call(n)
        setup[n] = time.monotonic() - t

    threads = [threading.Thread(target=warm, args=(n,)) for n in lanes]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    out_dir = os.path.join(ROOT, "chiprun_out", "op_profile")
    os.makedirs(out_dir, exist_ok=True)
    result = {
        "label": args.label,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()},
        "first_call_s": {str(n): round(s, 1) for n, s in setup.items()},
        "shapes": {},
    }
    for n in lanes:
        call(n)  # warm, untimed
        t = time.perf_counter()
        out = call(n)
        wall_ms = (time.perf_counter() - t) * 1e3
        trace_dir = tempfile.mkdtemp(prefix=f"op_profile_{n}_")  # hundreds of MB: not under chiprun_out/
        jax.profiler.start_trace(trace_dir)
        try:
            call(n)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        red = reduce_ops(path)
        red["wall_ms_untraced"] = wall_ms
        red["lanes_ok"] = int(out[1].sum())
        result["shapes"][str(n)] = red
        shutil.rmtree(trace_dir, ignore_errors=True)
    with open(os.path.join(out_dir, f"{args.label}.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    sys.stdout.flush()
    os._exit(0)  # the program's daemon threads have no shutdown (PERF.md §7)


if __name__ == "__main__":
    sys.exit(main())

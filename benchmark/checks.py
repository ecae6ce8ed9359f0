#!/usr/bin/env python3
"""Chip-side checks that are not runs of the benchmark, one process each so a
cell's programs are set up once:

    python3 benchmark/checks.py seeds --workload <cell> --seeds 12 --seconds 6
        the cell on a dozen seeds at its own size and load, a short window
        each: ``correct`` has to be true on every seed, and false under every
        control (a degraded variant of what was observed, each breaking one
        guarantee the configuration states).
    python3 benchmark/checks.py sweep --workload air4-transfer.paced --seconds 30
        the paced mix's batches with no pacing: the median block-path time,
        and the ``tick_s`` that puts the schedule at 4/5 of that rate.

Refuses to run off the chip, like the benchmark. Results also land in
``chiprun_out/``."""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from statistics import median

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def build(workload: str, seed: int, seconds: float, **traffic_override):
    from benchmark import manifest
    from benchmark.spans import Spans

    _cell, config, traffic = manifest.resolve(manifest.load(), workload)
    c = manifest.driver_of(config).Cell(config, dict(traffic, **traffic_override), seed, Spans())
    c.setup(seconds)
    return c


def seeds(args, say) -> int:
    import random

    from benchmark.run import judge

    bad = 0
    rows = []
    for seed in random.Random(args.first_seed).sample(range(2**31, 2**31 + 10**6), args.seeds):
        # a back-to-back mix signs its whole corpus in set-up: a short window
        # needs a short one (ignored by mixes that have no such key)
        cell = build(args.workload, seed, args.seconds,
                     corpus_batches=int(args.seconds * 3) + 4)
        try:
            cell.window(args.seconds)
            cell.after_window()
            sound = cell.compare(cell.observe())
            ok = judge(sound, lambda _m: None)
            row = {"seed": seed, "blocks": cell.window_blocks, "correct": ok,
                   "compared": {c["name"]: c["value"] for c in sound}, "controls": {}}
            for name, degrade in cell.controls().items():
                seen = cell.observe()
                degrade(seen)
                got = cell.compare(seen)
                row["controls"][name] = {
                    "correct": judge(got, lambda _m: None),
                    "outside": {c["name"]: c["value"] for c in got if c["value"] > c["limit"]},
                }
        finally:
            cell.close()
        fooled = [n for n, c in row["controls"].items() if c["correct"]]
        bad += (not ok) + len(fooled)
        say(f"seed {seed}: {row['blocks']} blocks, correct={ok}, controls "
            + ", ".join(f"{n}={c['outside']}" for n, c in row["controls"].items()))
        rows.append(row)
    out = os.path.join(ROOT, "chiprun_out", f"checks_{args.workload}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    say(f"{len(rows)} seeds, {bad} expectation(s) broken -> {out}")
    return 1 if bad else 0


def sweep(args, say) -> int:
    cell = build(args.workload, args.first_seed, args.seconds,
                 tick_s=0, corpus_batches=int(args.seconds * 4) + 8)
    try:
        cell.window(args.seconds)
    finally:
        cell.close()
    block_ms = [s["block_ms"] for s in cell.series]
    med = median(block_ms)
    tick = math.ceil(med / 1e3 / 0.8 / 0.05 - 1e-9) * 0.05
    doc = {"workload": args.workload, "blocks": len(block_ms), "median_block_ms": med,
           "min_block_ms": min(block_ms), "max_block_ms": max(block_ms),
           "sustained_batches_per_s": 1e3 / med, "tick_s": round(tick, 2),
           "series_block_ms": block_ms}
    out = os.path.join(ROOT, "chiprun_out", "sweep_tick_s.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    say("sweep " + json.dumps({k: v for k, v in doc.items() if k != "series_block_ms"}))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("seeds", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=23)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--off-chip", action="store_true", help="rehearsal on the CPU; proves nothing")
    args = ap.parse_args(argv)

    import time

    from benchmark import manifest
    from benchmark.run import device_doc

    t0 = time.monotonic()

    def say(msg: str) -> None:
        print(f"[{time.monotonic() - t0:7.1f}s] {msg}", flush=True)

    chips = manifest.cell(manifest.load(), args.workload)["chips"]
    say(f"device {device_doc(chips, require_chip=not args.off_chip)}")
    from fisco_bcos_tpu.observability.device import install_observatory

    install_observatory()
    return {"seeds": seeds, "sweep": sweep}[args.what](args, say)


if __name__ == "__main__":
    rc = main()
    # The program's daemon threads (plane worker, observatory probes) have no
    # shutdown; interpreter finalisation under them now and then aborts with
    # "terminate called ... FATAL: exception not rethrown" after the result
    # is out. Everything is flushed and no child process exists: leave.
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)

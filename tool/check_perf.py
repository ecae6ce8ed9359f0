#!/usr/bin/env python
"""Diff two pipeline round artifacts — the mechanical half of the
throughput campaign's "each win proved per stage" acceptance.

``bench.py --telemetry`` writes ``bench_telemetry.flood.pipeline.json``
per round: flood TPS plus the per-stage self-time vector aggregated across
every sampled tx in the flood window (``stage_self_ms``). Since ISSUE 13
it also writes ``bench_telemetry.flood.device.json``: the device
observatory's per-op measured phase vector
(``op_phase_ms``). Since ISSUE 16 it also writes
``bench_telemetry.flood.rounds.json``: the fleet observatory's aligned
consensus-round view — per-phase span p95 across every replica and round
(``round_phase_ms``: prepare/commit/execute/checkpoint/durable) plus the
quorum-edge skew percentiles (``skew_ms``). Since ISSUE 19 it also writes
``bench_telemetry.flood.storage.json``: the storage observatory's
commit-path vector (``storage_commit``: codec bytes per block, entries
copied per block, per-shard 2PC prepare/commit p95). This tool compares
two artifacts of ANY of these shapes (OLD then NEW) and exits nonzero
when:

- any stage's self time REGRESSED by >= --threshold (default 20%) — with
  an absolute floor (--min-ms, default 5 ms) so microsecond stages can't
  trip the gate on noise; or
- any device op's EXECUTE phase regressed by the same gates (the compile
  phase is excluded on purpose: cold-vs-warm cache variance is not a
  kernel regression — it shows separately as ``cold_compiles``); or
- any consensus phase's round-span p95 regressed by the same gates, or
  the fleet's quorum-edge skew p95 did; or
- any commit-path storage series (codec bytes/block, entries copied per
  block, shard 2PC p95) regressed by the same gates; or
- flood TPS dropped by >= --tps-threshold (default 20%).

Improvements are reported, never fatal. Stages present in only one
artifact are reported as added/removed (informational — a refactor may
legitimately rename a stage; renames that HIDE a regression still show as
a TPS drop).

Usage::

    python tool/check_perf.py OLD.json NEW.json [--threshold 0.2]
        [--min-ms 5] [--tps-threshold 0.2]

Exit 0 = no regression, 1 = regression(s) named on stdout, 2 = bad input.
"""

from __future__ import annotations

import argparse
import json
import sys


def load_artifact(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if not any(
        k in doc
        for k in (
            "stage_self_ms",
            "flood_tps",
            "op_phase_ms",
            "round_phase_ms",
            "storage_commit",
        )
    ):
        raise ValueError(
            f"{path}: not a round artifact (expected stage_self_ms, "
            "op_phase_ms, round_phase_ms, storage_commit and/or "
            "flood_tps keys)"
        )
    return doc


def diff(
    old: dict,
    new: dict,
    threshold: float = 0.2,
    min_ms: float = 5.0,
    tps_threshold: float = 0.2,
) -> tuple[list[str], list[str]]:
    """Returns (regressions, notes) — regressions nonempty = gate fails."""
    regressions: list[str] = []
    notes: list[str] = []

    def diff_series(
        kind: str, noun: str, old_map: dict, new_map: dict, unit: str = " ms"
    ):
        for name in sorted(set(old_map) | set(new_map)):
            o = old_map.get(name)
            n = new_map.get(name)
            if o is None:
                notes.append(f"{kind} added: {name} ({n:.1f}{unit})")
                continue
            if n is None:
                notes.append(f"{kind} removed: {name} (was {o:.1f}{unit})")
                continue
            if n - o >= min_ms and (o <= 0 or (n / o - 1.0) >= threshold):
                # o == 0 with a real delta is an unbounded regression, not
                # a skip — a series idle last round must not regress free
                grew = (
                    f"+{(n / o - 1.0) * 100.0:.0f}%" if o > 0 else "from zero"
                )
                regressions.append(
                    f"{kind} {name}: {noun} {o:.1f} -> {n:.1f}{unit} "
                    f"({grew}, threshold {threshold * 100.0:.0f}%)"
                )
            elif o - n >= min_ms and n > 0 and (o / n - 1.0) >= threshold:
                notes.append(
                    f"{kind} {name}: improved {o:.1f} -> {n:.1f}{unit} "
                    f"(-{(1.0 - n / o) * 100.0:.0f}%)"
                )

    diff_series(
        "stage", "self time",
        old.get("stage_self_ms") or {}, new.get("stage_self_ms") or {},
    )
    # device artifacts: gate on the measured SYNC phase per op, the host
    # waiting for the device's result (compile variance is cache state, not
    # kernel speed — it has its own cold_compiles row). An artifact from
    # before the phases were measured has no such row: its ops read as
    # added, not compared with the old remainder `execute`.
    diff_series(
        "device op", "sync time",
        {
            op: ph["sync"]
            for op, ph in (old.get("op_phase_ms") or {}).items()
            if "sync" in ph
        },
        {
            op: ph["sync"]
            for op, ph in (new.get("op_phase_ms") or {}).items()
            if "sync" in ph
        },
    )
    # fleet-round artifacts: per-consensus-phase span p95 across every
    # replica and aligned round, plus the quorum-edge skew p95 (ISSUE 16)
    diff_series(
        "round phase", "span p95",
        old.get("round_phase_ms") or {}, new.get("round_phase_ms") or {},
    )
    diff_series(
        "fleet", "skew p95",
        {
            "quorum_edge_skew": (old.get("skew_ms") or {}).get("p95", 0.0)
        } if "round_phase_ms" in old else {},
        {
            "quorum_edge_skew": (new.get("skew_ms") or {}).get("p95", 0.0)
        } if "round_phase_ms" in new else {},
    )
    # storage-commit artifacts (ISSUE 19): codec bytes/block, entries
    # copied per block and per-shard 2PC p95 — mixed units, so the diff
    # prints bare numbers; the same relative + absolute-floor gates apply
    # (codec bytes/block sits in the thousands, far above the floor)
    diff_series(
        "storage", "commit path",
        old.get("storage_commit") or {}, new.get("storage_commit") or {},
        unit="",
    )
    o_tps, n_tps = old.get("flood_tps"), new.get("flood_tps")
    if o_tps and n_tps is not None:
        if n_tps < o_tps * (1.0 - tps_threshold):
            regressions.append(
                f"flood TPS: {o_tps:.1f} -> {n_tps:.1f} "
                f"(-{(1.0 - n_tps / o_tps) * 100.0:.0f}%, threshold "
                f"{tps_threshold * 100.0:.0f}%)"
            )
        elif n_tps > o_tps * (1.0 + tps_threshold):
            notes.append(
                f"flood TPS: improved {o_tps:.1f} -> {n_tps:.1f} "
                f"(+{(n_tps / o_tps - 1.0) * 100.0:.0f}%)"
            )
    return regressions, notes


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("old", help="previous round's pipeline artifact (JSON)")
    ap.add_argument("new", help="this round's pipeline artifact (JSON)")
    ap.add_argument(
        "--threshold", type=float, default=0.2,
        help="relative per-stage self-time regression gate (default 0.20)",
    )
    ap.add_argument(
        "--min-ms", type=float, default=5.0,
        help="absolute floor: deltas under this many ms never regress",
    )
    ap.add_argument(
        "--tps-threshold", type=float, default=0.2,
        help="relative flood-TPS drop gate (default 0.20)",
    )
    args = ap.parse_args(argv)
    try:
        old = load_artifact(args.old)
        new = load_artifact(args.new)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"ERROR: {e}")
        return 2
    regressions, notes = diff(
        old, new, args.threshold, args.min_ms, args.tps_threshold
    )
    for n in notes:
        print(f"note: {n}")
    if regressions:
        for r in regressions:
            print(f"REGRESSION: {r}")
        print(f"FAIL: {len(regressions)} regression(s) between artifacts")
        return 1
    print("PASS: no per-stage self-time or flood-TPS regression")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Reduction from a profiler trace to numbers: device busy union, idle share,
device time by program, and idle gaps attributed to the benchmark's own host
spans. Pure Python over plain events, so the same code runs on the chip's
trace and on the small recorded trace the tests keep.

An event is ``{"plane", "line", "name", "start_ns", "dur_ns"}``. Device planes
are ``/device:TPU:<n>``; on them the ``XLA Modules`` line holds one event per
executed program and the ``XLA Ops`` line one per executed HLO op, loop bodies
included: millions a second for the EC program (5.8 million in 0.5 busy
seconds, my chip run, PR 23), which no run has the time to read. So the device
is busy while a program of the modules line runs, and the op lines are read
only for a device that has no modules line. Host spans are the ``bench.*``
TraceAnnotations, on whichever host line they landed."""

from __future__ import annotations

import re

WINDOW_SPAN = "bench.traced_window"
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


def load_xplane(path: str) -> list[dict]:
    """The events of an ``.xplane.pb`` the reduction needs: every device
    event, and the host's ``bench.*`` spans."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        device = is_device_plane(plane.name)
        lines = list(plane.lines)
        if device and any(line.name == _MODULES_LINE for line in lines):
            lines = [line for line in lines if line.name == _MODULES_LINE]
        for line in lines:
            for ev in line.events:
                if device or ev.name.startswith("bench."):
                    events.append({
                        "plane": plane.name, "line": line.name, "name": ev.name,
                        "start_ns": int(ev.start_ns), "dur_ns": int(ev.duration_ns),
                    })
    return events


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name.upper().split(":")[1]


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Sorted, disjoint union of half-open intervals."""
    out: list[tuple[int, int]] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def _clip(intervals, lo: int, hi: int):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def program_name(module_event_name: str) -> str:
    """``jit__admission_packed(8123456789)`` -> ``jit__admission_packed``:
    the fingerprint changes with every compile, the program's name does not."""
    return re.sub(r"\(\d+\)$", "", module_event_name).strip()


def reduce_trace(events: list[dict]) -> dict | None:
    """-> window_s, busy_s (mean over devices), idle_share, program_s
    {program: seconds, mean over devices}, gaps_s {host span: idle seconds},
    calls {program: executions on the busiest device}. None where the trace
    holds no traced window or no device event: nothing to read."""
    marks = [e for e in events if e["name"] == WINDOW_SPAN]
    if not marks:
        return None
    lo = min(e["start_ns"] for e in marks)
    hi = max(e["start_ns"] + e["dur_ns"] for e in marks)
    by_device: dict[str, list[dict]] = {}
    for e in events:
        if is_device_plane(e["plane"]):
            by_device.setdefault(e["plane"], []).append(e)
    if not by_device or hi <= lo:
        return None
    busy_ns, program_ns, calls = [], {}, {}
    idle: list[tuple[int, int]] = []
    for plane, evs in sorted(by_device.items()):
        ran = (
            [e for e in evs if e["line"] == _MODULES_LINE]
            or [e for e in evs if e["line"] == _OPS_LINE]
            or evs
        )
        busy = _clip(union([(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ran]), lo, hi)
        busy_ns.append(sum(b - a for a, b in busy))
        if not idle:  # gaps are attributed on the first device
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
        seen: dict[str, int] = {}
        for e in evs:
            if e["line"] != _MODULES_LINE:
                continue
            a, b = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
            if b > a:
                name = program_name(e["name"])
                program_ns[name] = program_ns.get(name, 0) + (b - a)
                seen[name] = seen.get(name, 0) + 1
        for name, n in seen.items():
            calls[name] = max(calls.get(name, 0), n)
    n_dev = len(by_device)
    spans = [
        (e["name"], e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
        if e["name"].startswith("bench.") and e["name"] != WINDOW_SPAN
        and not is_device_plane(e["plane"])
    ]
    gaps_ns: dict[str, int] = {}
    for a, b in idle:
        # cut the gap where a span starts or ends; each piece goes to the
        # innermost (shortest) span that covers it, or to none
        cuts = sorted({a, b} | {x for _n, s, t in spans for x in (s, t) if a < x < b})
        for lo_, hi_ in zip(cuts, cuts[1:]):
            over = [(t - s, name) for name, s, t in spans if s <= lo_ and t >= hi_]
            name = min(over)[1] if over else "(no span)"
            gaps_ns[name] = gaps_ns.get(name, 0) + (hi_ - lo_)
    busy_s = sum(busy_ns) / n_dev / 1e9
    window_s = (hi - lo) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "devices": n_dev,
        "program_s": {k: v / n_dev / 1e9 for k, v in program_ns.items()},
        "calls": calls,
        "gaps_s": {k: v / 1e9 for k, v in gaps_ns.items()},
    }


def breakdown(red: dict) -> dict:
    """The result line's ``breakdown``: device time by program and idle gaps
    by host span, ten of each at most, largest first."""
    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"device_ops": top(red["program_s"]), "idle_gaps": top(red["gaps_s"])}

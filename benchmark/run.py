#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

New process, refuses to run off the chip, builds the cell from the seed, warms
only the cell's own shapes (set-up), measures for ``--seconds``, checks what
the timed path produced against the plain reference, prints each number
compared beside its limit and, as the last line of standard output, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
with ``--trace 1``, ``breakdown``. ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` its per-layer metrics, with the profiler on for
``trace_blocks`` more blocks after the window has closed.

What belongs to a configuration, a traffic mix or a per-layer metric is a
file found by its name in BENCHMARK.json (benchmark/manifest.py,
benchmark/README.md)."""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RC_NO_PROGRAM = 3  # the checkout holds the benchmark but not the program
RC_NO_CHIP = 4


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="also write the reduced trace's events to this JSON file")
    return ap.parse_args(argv)


def device_doc(chips: int, require_chip: bool) -> dict:
    """Device identity as JAX reports it; exits off the chip."""
    try:
        from fisco_bcos_tpu.utils.jaxenv import configure_compile_cache, device_identity
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout ({e})", file=sys.stderr)
        raise SystemExit(RC_NO_PROGRAM) from None
    cache_dir = configure_compile_cache()
    try:
        ident = device_identity()
    except Exception as e:  # a backend that fails to initialise: no chip
        print(f"benchmark: JAX found no device: {type(e).__name__}: {e}", file=sys.stderr)
        raise SystemExit(RC_NO_CHIP) from None
    if require_chip and (ident["platform"] != "tpu" or ident["count"] < chips):
        print(
            f"benchmark: needs {chips} TPU chip(s), JAX reports "
            f"{ident['count']} x {ident['platform']}", file=sys.stderr,
        )
        raise SystemExit(RC_NO_CHIP)
    return {"platform": ident["platform"], "kind": ident["device_kind"],
            "count": ident["count"], "cache_dir": cache_dir}


def memory_peak_bytes() -> int:
    import jax

    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.local_devices()
    )


def take_trace(cell, spans, blocks: int, workload: str, keep: str, say):
    """Profile ``blocks`` more blocks at the window's cadence -> reduction."""
    import jax

    from benchmark import trace_reduce

    trace_dir = os.path.join(ROOT, ".bench_trace", workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    # the device planes and the bench.* annotations; no Python call tracer,
    # which would slow the host path it is looking at
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with spans.span(trace_reduce.WINDOW_SPAN):
            cell.traced(blocks)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    events = trace_reduce.load_xplane(files[0])
    lines: dict[str, int] = {}
    for e in events:
        key = f"{e['plane']} | {e['line']}"
        lines[key] = lines.get(key, 0) + 1
    say("trace lines " + json.dumps(lines))
    if keep:
        os.makedirs(os.path.dirname(os.path.abspath(keep)), exist_ok=True)
        with open(keep, "w") as f:
            json.dump(events, f)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return trace_reduce.reduce_trace(events)


def summary(series: list[dict]) -> dict:
    """Quartiles of each per-block reading, and the medians of the window's
    thirds: a drift or an outlier shows here without reading the series."""
    from statistics import median, quantiles

    out = {"blocks": len(series)}
    for key in ("block_ms", "commit_ms", "admit_ms"):
        v = [s[key] for s in series if key in s]
        if len(v) >= 6:
            q1, q2, q3 = quantiles(v, n=4)
            third = len(v) // 3
            out[key] = {
                "min": round(min(v), 2), "q1": round(q1, 2), "median": round(q2, 2),
                "q3": round(q3, 2), "max": round(max(v), 2),
                "thirds": [round(median(v[i * third:(i + 1) * third]), 2) for i in range(3)],
            }
    return out


def judge(comparisons: list[dict], say) -> bool:
    ok = True
    for c in comparisons:
        within = c["value"] <= c["limit"]
        ok &= within
        say(f"compared {c['name']}: {c['value']} (limit {c['limit']})"
            f"{'' if within else '  <-- outside'}")
    return ok


def run(args, require_chip: bool = True, out=sys.stdout) -> dict:
    from benchmark import counters, manifest, trace_reduce
    from benchmark.spans import Spans

    def say(msg: str) -> None:
        print(f"[{time.monotonic() - T_PROCESS:7.1f}s] {msg}", file=out, flush=True)

    doc = manifest.load()
    workload, config, traffic = manifest.resolve(doc, args.workload)
    t = time.monotonic()
    device = device_doc(int(workload["chips"]), require_chip)
    if device["platform"] == "tpu":
        from benchmark.peaks import peaks_for

        peaks_for(device["kind"])
    from fisco_bcos_tpu.observability.device import install_observatory

    install_observatory()  # the compile ledger's hooks, before the first compile
    cache_dir = device.pop("cache_dir")
    say(f"{args.workload} seed {args.seed} on {device['count']} x {device['kind']} "
        f"({device['platform']}); compile cache {cache_dir}")
    spans = Spans()
    cell = manifest.driver_of(config).Cell(config, traffic, args.seed, spans)
    parts = {"import_s": t - T_PROCESS, "backend_s": time.monotonic() - t}
    try:
        cell.setup(args.seconds)
        parts.update(cell.setup_parts)
        gc.collect()
        gc.freeze()  # the corpus is not the node's collector's business
        c0 = counters.snapshot()
        setup_s = time.monotonic() - T_PROCESS
        say("set-up " + json.dumps({k: round(v, 2) for k, v in parts.items()})
            + f" total {setup_s:.2f}s")
        cell.window(args.seconds)
        c1 = counters.snapshot()
        say("series " + json.dumps(cell.series))
        say("series summary " + json.dumps(summary(cell.series)))
        say("counters " + json.dumps(counters.delta(c0, c1)))
        red = None
        if args.trace:
            red = take_trace(cell, spans, int(traffic["trace_blocks"]), args.workload,
                             args.keep_trace, say)
            ct1 = counters.snapshot()
            say("trace " + json.dumps(red))
        else:
            ct1 = c1
        peak = memory_peak_bytes()
        cell.after_window()
        correct = judge(cell.compare(cell.observe()), say)
    finally:
        cell.close()

    ctx = types.SimpleNamespace(cell=cell, spans=spans, c0=c0, c1=c1, ct1=ct1, red=red,
                                t0=cell.t0, t1=cell.t1)
    metrics = {}
    if args.trace:
        for m in manifest.metrics_of(doc, "per_layer", args.workload):
            value = manifest.reader_of(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        seen = dict(cell.end_to_end(), setup_s=setup_s)
        for m in manifest.metrics_of(doc, "end_to_end", args.workload):
            metrics[m["name"]] = {"value": seen[m["name"]], "unit": m["unit"]}
    device["memory_peak_bytes"] = peak
    line = {"correct": bool(correct), "attempted": cell.attempted,
            "failed": cell.failed_count(), "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        line["breakdown"] = trace_reduce.breakdown(red)
    return line


def main(argv=None) -> int:
    args = parse(argv)
    line = run(args)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    # The program's daemon threads (plane worker, observatory probes) have no
    # shutdown; interpreter finalisation under them now and then aborts with
    # "terminate called ... FATAL: exception not rethrown" after the result
    # is out. Everything is flushed and no child process exists: leave.
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)

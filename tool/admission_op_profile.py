#!/usr/bin/env python3
"""Device-op profile of the fused admission programs, one call a shape, and
the chip-free tally of their exact carry chains.

Runs ``crypto.admission._admit_batch_device`` (the program every benchmark
cell waits for) at every benchmark shape — 500 lanes (bucket 512), 1,000
(1,024) and 10,000 (10,240) of secp256k1 + keccak256, 10,000 of SM2 + SM3
(``sm:10000``), and ``10000/4``: the 2,560 lanes one chip of a four-chip host
is given of the 10,000-lane block (``verify10k-quad.stream``: the first
quarter of the bucket's operands through the same body; the mesh program adds
the all_gather of the packed result) — under ``jax.profiler`` with the
device's ops line on, and reduces the capture to: device ops a call, the op
kinds by count and by time, the program's device time. The mechanism counter
of PERF.md §6 (PR 25).

    python tool/admission_op_profile.py --label parent [--lanes 1000,sm:10000,10000/4]

Refuses to run off the chip (exit 4): an op count of the CPU backend says
nothing about the TPU's fusions. Writes ``chiprun_out/op_profile/<label>.json``
and prints the same JSON as its last line.

    python tool/admission_op_profile.py --chains

needs no chip and compiles nothing: it traces ``admission_core`` and
``sm_admission_core`` at 1,024 lanes and counts the exact carry chains a call
executes (``limb._carry_in``; ``lax.scan`` bodies weighted by their trip
counts) and their packed lookahead words (Σ ⌈limbs / 32⌉), by the field
operation that runs them. The count is static, the same on every backend
(PERF.md §6, PR 27); ``tests/test_limb_lane_dense.py`` pins the totals.
"""

from __future__ import annotations

import argparse
import collections
import concurrent.futures
import functools
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


# -- the chain tally (tracing only) -------------------------------------------

# frames of ops/limb.py between a field operation and _carry_in
_CHAIN_HELPERS = ("_reduce_cols", "reduce_wide", "redc", "_redc_cols", "_table_fold")


def _chain_caller(frames) -> str:
    """The user frames of a ``_carry_in`` equation, innermost first -> the
    field operation that runs the chain and the helpers between them
    (``FoldField.mul>reduce_wide>carry_norm``); for a chain no field
    operation runs, the function outside ``ops/limb.py`` that asked for it."""
    path = []
    for f in frames[1:]:
        name = f.function_name
        if not f.file_name.endswith(os.path.join("ops", "limb.py")):
            path.append(f"{os.path.basename(f.file_name)}:{name}")
            break
        if "Field." in name and name.rpartition(".")[2] not in _CHAIN_HELPERS:
            path.append(name)
            break
        path.append(name.rpartition(".")[2])
    return ">".join(reversed(path))


def chain_tally(jaxpr, weight: int = 1, out=None) -> collections.Counter:
    """Executed ``limb._carry_in`` calls of a jaxpr -> {(caller, limbs): n},
    bodies of ``scan`` multiplied by their trip counts. A call is known by its
    one ``iota`` equation (the bit index along the limb axis), whose shape
    gives the chain's limbs."""
    import jax
    from jax._src import source_info_util

    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "iota":
            frames = list(source_info_util.user_frames(eqn.source_info.traceback))
            if frames and frames[0].function_name == "_carry_in":
                out[_chain_caller(frames), eqn.params["shape"][0]] += weight
        if eqn.primitive.name == "while":
            raise ValueError("chain tally: a while loop has no static trip count")
        inner = weight * eqn.params["length"] if eqn.primitive.name == "scan" else weight
        for sub in jax.core.jaxprs_in_params(eqn.params):
            chain_tally(sub, inner, out)
    return out


def chain_totals(tally) -> dict:
    return {
        "chains": sum(tally.values()),
        "words": sum(n * -(-limbs // 32) for (_, limbs), n in tally.items()),
    }


CHAIN_PROGRAMS = {  # name -> (the unjitted body, the PROGSPEC entry with its operands)
    "secp": ("admission_core", "admission_core"),
    "sm": ("sm_admission_core", "_sm_admission_packed"),
}


def program_chains(program: str, lanes: int = 1024) -> collections.Counter:
    """The chain tally of one admission program, traced at `lanes` lanes."""
    import jax

    from fisco_bcos_tpu.crypto import admission

    body, spec = CHAIN_PROGRAMS[program]
    operands = [
        jax.ShapeDtypeStruct(shape, dtype)
        for shape, dtype in admission.PROGSPEC[spec]["inputs"](lanes)
    ]
    return chain_tally(jax.make_jaxpr(getattr(admission, body))(*operands).jaxpr)


def chains_main() -> int:
    result = {}
    for program in CHAIN_PROGRAMS:
        tally = program_chains(program)
        result[program] = dict(
            chain_totals(tally),
            by_caller=[
                {"caller": caller, "limbs": limbs, "chains": n}
                for (caller, limbs), n in sorted(tally.items(), key=lambda kv: -kv[1])
            ],
        )
        print(f"{program}: {result[program]['chains']} exact chains a call, "
              f"{result[program]['words']} lookahead words")
        for row in result[program]["by_caller"]:
            print(f"  {row['chains']:>7}  {row['caller']} ({row['limbs']})")
    print(json.dumps(result))
    return 0


# -- the device-op profile (chip only) ----------------------------------------

# a shape of --lanes: [sm:]N[/shards][@[tile][rows|dense]]
_SHAPE = re.compile(
    r"(?:(?P<suite>sm):)?(?P<n>\d+)(?:/(?P<shards>\d+))?"
    r"(?P<plan>@(?P<tile>\d+)?(?P<form>rows|dense)?)?$"
)


def parse_shape(shape: str) -> dict:
    """``1000`` / ``sm:10000`` / ``10000/4``: `n` signatures (their bucket's
    lanes, or the first of `shards` equal parts of it) through the body as the
    program's own plan runs them. ``@`` names a plan instead
    (``limb.lane_plan``'s two halves): ``10000/4@1280`` the quarter bucket in
    tiles of 1,280 lanes, ``10000/4@rows`` whole in ``[16, 2560]``,
    ``10000/4@dense`` whole in ``[16, 20, 128]``, ``10000/4@1024rows`` both;
    a half left out is the whole batch, or the rule's form for the tile."""
    m = _SHAPE.match(shape)
    if m is None or (m["plan"] and not (m["tile"] or m["form"])):
        raise ValueError(f"--lanes: cannot read {shape!r}")
    return {
        "suite": m["suite"] or "secp",
        "n": int(m["n"]),
        "shards": int(m["shards"] or 1),
        "planned": bool(m["plan"]),
        "tile": int(m["tile"]) if m["tile"] else None,
        "dense": {"rows": False, "dense": True, None: None}[m["form"]],
    }


def shard_operands(body, block, shards: int):
    """What one chip of a mesh of `shards` is given of `block`: the first of
    the equal parts of the bucket's operands (no bucket of the one-chip
    ladder has that size, so `_admit_batch_device` cannot be asked for it)."""
    import numpy as np

    from fisco_bcos_tpu.ops.hash_common import bucket_batch

    payloads = list(block["payloads"])
    bb = bucket_batch(len(payloads))
    operands = body.marshal(payloads, np.asarray(block["sigs"], dtype=np.uint8), bb)
    return tuple(o[: bb // shards] for o in operands)


def lower_plan(whole, operands, tile, dense):
    """`whole` over `operands` in tiles of `tile` lanes (None: one tile), a
    tile in the form `dense` says (None: the rule's), as one jitted program,
    traced and lowered here -> (the lowering, the plan it was traced under).
    The form is forced where the rule is asked (``limb._whole_dense``), for
    the time of the trace; jit's trace caches are dropped first, because a
    nested jit of the same shape remembers the form it was traced in."""
    import jax

    from fisco_bcos_tpu.crypto import admission
    from fisco_bcos_tpu.ops import limb

    lanes = operands[0].shape[0]
    tile = tile or lanes
    rule = limb._whole_dense
    if dense is None:
        dense = rule(limb._padded(tile))

    def program(*ops):
        return admission._in_tiles(whole, tile, *ops)

    program.__name__ = f"admission_plan_{tile}_{'dense' if dense else 'rows'}"
    asked = []
    jax.clear_caches()
    limb._whole_dense = lambda lanes: asked.append(lanes) or dense
    try:
        lowered = jax.jit(program).lower(*operands)
    finally:
        limb._whole_dense = rule
        jax.clear_caches()
    if not asked:
        raise RuntimeError(f"{program.__name__}: the trace never asked for its form")
    plan = {"lanes": lanes, "tile": tile, "tiles": -(-lanes // tile),
            "form": "dense" if dense else "rows"}
    return lowered, plan


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--label")
    ap.add_argument("--lanes", default="500,1000,10000,sm:10000,10000/4",
                    help="shapes, comma-separated: [sm:]N[/shards][@[tile][rows|dense]]")
    ap.add_argument("--seed", type=int, default=2500000001)
    ap.add_argument("--compilers", type=int, default=5,
                    help="shapes compiled side by side")
    ap.add_argument("--chains", action="store_true",
                    help="no chip: trace the two programs and count their exact carry chains")
    args = ap.parse_args()
    if args.chains:
        return chains_main()
    if not args.label:
        ap.error("--label is required for the device-op profile")
    shapes = args.lanes.split(",")
    specs = {shape: parse_shape(shape) for shape in shapes}

    from fisco_bcos_tpu.utils import jaxenv

    jaxenv.configure_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"refused: op profile needs the chip, got {dev.platform}", file=sys.stderr)
        return 4

    import numpy as np

    from benchmark.generators import signed_payloads, sm_signed_payloads
    from fisco_bcos_tpu.crypto import admission

    suites = {
        "secp": (signed_payloads.Corpus, admission._SECP, admission._admission_whole),
        "sm": (sm_signed_payloads.Corpus, admission._SM, admission._sm_admission_packed),
    }
    # a shape's first call traces and compiles (or loads). Tracing holds the
    # interpreter, and a named plan is traced under a forced rule, so the
    # shapes are traced one after another here; each compiles on the pool
    # while the next is traced, `--compilers` of them at a time (a compile
    # peaks at 3.5 GiB of the host's memory and most of it stays with the
    # process: a dozen shapes a process is what 40 GiB hold)
    pool = concurrent.futures.ThreadPoolExecutor(args.compilers)
    slots = threading.Semaphore(args.compilers)
    corpora, calls, plans, setup, warming = {}, {}, {}, {}, []

    def warm(shape, lowered, t0, operands=None):
        try:
            if lowered is not None:
                calls[shape] = functools.partial(_packed_call, lowered.compile(), operands)
                del lowered
                _give_back()
            calls[shape]()
        finally:
            slots.release()
        setup[shape] = time.monotonic() - t0
        print(f"[op profile] {shape}: first call after {setup[shape]:.1f} s, "
              f"process at {_rss_gib():.1f} GiB", file=sys.stderr, flush=True)

    # the named plans first: no shape is traced under the rule while it is forced
    for shape, spec in sorted(specs.items(), key=lambda kv: not kv[1]["planned"]):
        slots.acquire()
        t0 = time.monotonic()
        corpus, body, whole = suites[spec["suite"]]
        key = spec["suite"], spec["n"]
        if key not in corpora:
            corpora[key] = corpus(
                {"lanes": spec["n"], "signers": 64, "rotations": 1}, args.seed).blocks[0]
        block = corpora[key]
        if not spec["planned"] and spec["shards"] == 1:
            # the program every cell waits for, through the device leg itself
            calls[shape] = functools.partial(
                admission._admit_batch_device, block["payloads"], block["sigs"], body=body)
            warming.append(pool.submit(warm, shape, None, t0))
            continue
        operands = shard_operands(body, block, spec["shards"])
        if spec["planned"]:
            lowered, plans[shape] = lower_plan(whole, operands, spec["tile"], spec["dense"])
        else:  # a mesh's share as the program's own plan runs it
            lowered = jax.jit(body.packed).lower(*operands)
        warming.append(pool.submit(warm, shape, lowered, t0, jax.device_put(operands)))
        del lowered
    for w in warming:
        w.result()
    pool.shutdown()

    out_dir = os.path.join(ROOT, "chiprun_out", "op_profile")
    os.makedirs(out_dir, exist_ok=True)
    result = {
        "label": args.label,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()},
        "first_call_s": {shape: round(s, 1) for shape, s in setup.items()},
        "shapes": {},
    }
    for shape in shapes:
        call = calls[shape]
        call()  # warm, untimed
        t = time.perf_counter()
        out = call()
        wall_ms = (time.perf_counter() - t) * 1e3
        # hundreds of MB: not under chiprun_out/
        trace_dir = tempfile.mkdtemp(prefix=f"op_profile_{re.sub(r'[:/@]', '_', shape)}_")
        jax.profiler.start_trace(trace_dir)
        try:
            call()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        red = reduce_ops(path)
        red["wall_ms_untraced"] = wall_ms
        red["lanes_ok"] = int(np.asarray(out[1]).sum())
        if shape in plans:
            red["plan"] = plans[shape]
        result["shapes"][shape] = red
        shutil.rmtree(trace_dir, ignore_errors=True)
        # the table is long and a call may be cut: what is measured is kept
        with open(os.path.join(out_dir, f"{args.label}.json"), "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result))
    sys.stdout.flush()
    os._exit(0)  # the program's daemon threads have no shutdown (PERF.md §7)


def _give_back() -> None:
    """The compiler's freed memory back to the host (3.5 -> 1.9 GiB a
    compiled shape): glibc keeps it otherwise."""
    import ctypes

    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def _rss_gib() -> float:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / 2**30


def _packed_call(compiled, operands):
    """One call of a shape's compiled program on operands that are on the
    device already -> admit_batch's tuple for those lanes."""
    import numpy as np

    packed = np.asarray(compiled(*operands))
    return packed[:, :20], packed[:, 20] != 0, packed[:, 21:85], packed[:, 85:117]


if __name__ == "__main__":
    sys.exit(main())

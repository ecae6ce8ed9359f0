#!/usr/bin/env python
"""Benchmarks against BASELINE.md configs — one JSON line per metric,
headline (north-star) first.

Metrics:
1. secp256k1_admission_verifies_per_s_10k_block (headline): the fused
   keccak->recover->address device program over a 10k-tx block vs an
   OpenSSL-per-core CPU baseline (Transaction::verify(),
   bcos-txpool/sync/TransactionSync.cpp:521 hot loop).
2. block_verify_latency_ms_10k: wall latency of that same device program —
   the "block-verify latency" half of the north-star metric.
3. sm2_batch_verify_per_s_10k: national-crypto batch verify
   (SM2Crypto.cpp:29-91) vs per-core CPU SM2.
4. merkle_root_10k_leaves_ms: device wide-merkle over 10k keccak leaves
   (benchmark/merkleBench.cpp:36-67) vs a native-C sequential merkle/core.
5. e2e_flood_tps: FISCO_BENCH_FLOOD (default 3k) duplicated parallel-transfer txs
   (DupTestTxJsonRpcImpl_2_0.h flood) through a live FOUR-NODE PBFT chain
   (BASELINE config #4) — admission, payload gossip, three-phase consensus,
   replica re-execution x4, 2PC commit x4; vs_baseline is the reference's
   published 10k TPS claim (README.md:10).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# exit code of an --only child that found itself off the chip (and of the
# parent that stops at it): a run without a TPU measures nothing
RC_NOT_ON_CHIP = 4

# platform / device_kind / count of the child that measured — stamped on
# every metric line by _emit (filled by _init_jax; the storage child never
# touches JAX and stamps "host" itself)
_DEVICE: dict = {}


def _init_jax() -> None:
    """Compile cache + device identity — called by the --only children, NOT
    by the orchestrating parent, which never touches a device (one process
    per chip: a parent holding it would starve its children).

    A child that is not on ``tpu`` exits :data:`RC_NOT_ON_CHIP` before
    measuring anything, unless the caller pinned ``JAX_PLATFORMS=cpu``
    itself — then every line it prints says ``"platform": "cpu"``."""
    from fisco_bcos_tpu.utils.jaxenv import (
        configure_compile_cache,
        device_identity,
    )

    configure_compile_cache()
    ident = device_identity()
    if ident["platform"] != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(
            f"# bench refused: this process is on platform="
            f"{ident['platform']!r}, not 'tpu' — a number taken here is not "
            "a device number (set JAX_PLATFORMS=cpu yourself to measure the "
            "CPU on purpose)",
            flush=True,
        )
        raise SystemExit(RC_NOT_ON_CHIP)
    _DEVICE.update(ident)


class GateFailure(RuntimeError):
    """A bench child's own correctness gate failed: the child prints no
    value for that metric and exits non-zero."""


BLOCK_TXS = 10_000
UNIQUE = 64
FLOOD_TXS = int(os.environ.get("FISCO_BENCH_FLOOD", "3000"))

# (name, unit) of the fixed metrics — bench functions emit through these
M_SECP = ("secp256k1_admission_verifies_per_s_10k_block", "tx/s")
M_LATENCY = ("block_verify_latency_ms_10k", "ms")
M_SM2 = ("sm2_batch_verify_per_s_10k", "sig/s")
M_MERKLE = ("merkle_root_10k_leaves_ms", "ms")
M_FLOOD = ("e2e_flood_tps", "tx/s")
# requests per merged device dispatch during the flood (1.0 = no coalescing
# won; baseline is the plane-less per-caller dispatch, i.e. exactly 1.0)
M_COALESCE = ("device_plane_coalesce_ratio", "reqs/dispatch")
# p95 inter-node spread of the corrected quorum edge across the measured
# flood's aligned rounds (fleet observatory; 0 with FISCO_FLEET_OBS=0)
M_ROUND_SKEW = ("fleet_round_skew_ms_p95", "ms")
# commit-path copy amplification over the measured flood (ISSUE 19 storage
# observatory): entries copied per durably-written row, mean across the
# measured blocks (0 and unmeasured with FISCO_STORAGE_OBS=0)
M_STORAGE_AMP = ("storage_copy_amplification", "copies/row")
# the --only storage child's durable-backend batch-write leg; the other
# five (backend, op) rows/s lines ride along under their dynamic names
M_STORAGE_ROWS = ("storage_sqlite_write_rows_per_s", "rows/s")
def _emit(
    metric: str,
    value: float,
    unit: str,
    vs_baseline: float,
    error: str | None = None,
) -> None:
    rec = {
        "metric": metric,
        "value": round(value, 2),
        "unit": unit,
        "vs_baseline": round(vs_baseline, 2),
        "platform": _DEVICE.get("platform"),
        "device_kind": _DEVICE.get("device_kind"),
        "device_count": _DEVICE.get("count"),
    }
    if error:
        rec["error"] = error[:400]
    print(json.dumps(rec), flush=True)


def _cpu_secp_baseline_tps(digests, sigs65, pubs) -> float:
    """OpenSSL (cryptography pkg) single-thread verify TPS x core count."""
    ncpu = os.cpu_count() or 1
    try:
        from cryptography.hazmat.primitives import hashes
        from cryptography.hazmat.primitives.asymmetric import ec, utils
    except ImportError:
        return 15_000.0 * ncpu  # typical libsecp256k1-class figure
    keys = [
        ec.EllipticCurvePublicNumbers(x, y, ec.SECP256K1()).public_key()
        for x, y in pubs
    ]
    ders = [
        utils.encode_dss_signature(
            int.from_bytes(bytes(s[:32]), "big"),
            int.from_bytes(bytes(s[32:64]), "big"),
        )
        for s in sigs65[:UNIQUE]
    ]
    prehash = ec.ECDSA(utils.Prehashed(hashes.SHA256()))
    n_iter = 1000
    t0 = time.perf_counter()
    for i in range(n_iter):
        j = i % UNIQUE
        keys[j].verify(ders[j], digests[j], prehash)
    dt = time.perf_counter() - t0
    return n_iter / dt * ncpu


def bench_admission() -> None:
    from fisco_bcos_tpu.crypto.admission import admission_step
    from fisco_bcos_tpu.crypto.ref.keccak import keccak256
    from fisco_bcos_tpu.crypto.testvec import admission_tensors, signed_payload_vectors
    from fisco_bcos_tpu.ops.hash_common import bucket_batch, pad_rows

    payloads, sigs, digests, pubs = signed_payload_vectors(
        BLOCK_TXS,
        unique=UNIQUE,
        payload_fn=lambda i: b"bench parallel-transfer tx %06d" % i + b"\xab" * 64,
        secret_fn=lambda i: 0xBEEF + 104729 * i,
    )
    blocks, nblocks, r, s, v = admission_tensors(payloads, sigs)
    bb = bucket_batch(BLOCK_TXS)
    args = tuple(pad_rows(a, bb) for a in (blocks, nblocks, r, s, v))

    # correctness gate + jit warmup: device must match the CPU reference,
    # or the child measures nothing and exits non-zero
    addr, ok, *_rest = admission_step(*args)
    addr, ok = np.asarray(addr), np.asarray(ok)
    if not bool(ok[:BLOCK_TXS].all()):
        raise GateFailure("device admission rejected valid signatures")
    for j in (0, UNIQUE - 1):
        x, y = pubs[j]
        expect = keccak256(x.to_bytes(32, "big") + y.to_bytes(32, "big"))[12:]
        if bytes(addr[j].astype(np.uint8)) != expect:
            raise GateFailure(f"sender address mismatch in lane {j}")

    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = admission_step(*args)
        out[1].block_until_ready()
        times.append(time.perf_counter() - t0)
    best = min(times)
    tps = BLOCK_TXS / best

    cpu_tps = _cpu_secp_baseline_tps(digests, sigs, pubs)
    _emit(M_SECP[0], tps, M_SECP[1], tps / cpu_tps)
    cpu_block_ms = BLOCK_TXS / cpu_tps * 1000.0
    _emit(
        M_LATENCY[0],
        best * 1000.0,
        M_LATENCY[1],
        cpu_block_ms / (best * 1000.0),
    )


def bench_sm2() -> None:
    import hashlib

    from fisco_bcos_tpu.crypto.ref import ecdsa as ref
    from fisco_bcos_tpu.ops.sm2 import verify_batch

    n = BLOCK_TXS
    msgs, sigs, pubs = [], [], []
    for i in range(UNIQUE):
        d = 0x1234 + 7919 * i
        h = hashlib.sha256(b"sm2 bench %04d" % i).digest()
        r, s = ref.sm2_sign(h, d)
        msgs.append(h)
        sigs.append((r, s))
        pubs.append(ref.privkey_to_pubkey(ref.SM2_CURVE, d))

    def rep(arr):
        return np.tile(arr, (n // UNIQUE + 1, 1))[:n]

    hz = rep(np.stack([np.frombuffer(h, np.uint8) for h in msgs]))
    r_b = rep(np.stack([np.frombuffer(r.to_bytes(32, "big"), np.uint8) for r, _ in sigs]))
    s_b = rep(np.stack([np.frombuffer(s.to_bytes(32, "big"), np.uint8) for _, s in sigs]))
    pub_b = rep(
        np.stack(
            [
                np.frombuffer(x.to_bytes(32, "big") + y.to_bytes(32, "big"), np.uint8)
                for x, y in pubs
            ]
        )
    )

    ok = verify_batch(hz, r_b, s_b, pub_b)
    if not bool(np.asarray(ok)[:n].all()):
        raise GateFailure("sm2 device verify rejected valid sigs")
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        ok = verify_batch(hz, r_b, s_b, pub_b)
        np.asarray(ok)
        times.append(time.perf_counter() - t0)
    tps = n / min(times)

    # CPU baseline: the NATIVE C single-item SM2 verify x cores — the
    # honest stand-in for the reference's wedpr-Rust/OpenSSL-tassl path
    # (SM2Crypto.cpp:29-91, fast_sm2.cpp), replacing the old pure-Python
    # baseline that inflated vs_baseline ~50x
    from fisco_bcos_tpu import native_bind

    pub_bytes = [
        x.to_bytes(32, "big") + y.to_bytes(32, "big") for x, y in pubs
    ]
    es = [
        ref.sm2_e_bytes(pub_bytes[j], msgs[j]) for j in range(UNIQUE)
    ]
    err = None
    t0 = time.perf_counter()
    if native_bind.load() is not None:
        iters = 2000
        for i in range(iters):
            j = i % UNIQUE
            r, s = sigs[j]
            if not native_bind.sm2_verify(es[j], r, s, pub_bytes[j]):
                raise GateFailure("native sm2 verify rejected its own signature")
    else:
        iters = 20  # degraded: pure-Python fallback baseline
        err = "native baseline unavailable; pure-Python CPU baseline"
        for i in range(iters):
            j = i % UNIQUE
            r, s = sigs[j]
            if not ref.sm2_verify(msgs[j], r, s, pubs[j]):
                raise GateFailure(
                    "cpu reference sm2 verify rejected its own signature"
                )
    cpu_tps = iters / (time.perf_counter() - t0) * (os.cpu_count() or 1)
    _emit(M_SM2[0], tps, M_SM2[1], tps / cpu_tps, error=err)


def bench_merkle() -> None:
    import jax.numpy as jnp

    from fisco_bcos_tpu import native_bind
    from fisco_bcos_tpu.crypto.ref.keccak import keccak256
    from fisco_bcos_tpu.ops.merkle import MerkleTree, merkle_root

    n = BLOCK_TXS
    leaves = np.frombuffer(
        b"".join(keccak256(b"%d" % i) for i in range(256)) * (n // 256 + 1),
        dtype=np.uint8,
    )[: n * 32].reshape(n, 32).copy()

    # leaves live on device: in the sealing path tx/receipt hashes come out
    # of the batch hash kernels, so the root computation starts device-side
    dev_leaves = jnp.asarray(leaves)
    root = merkle_root(dev_leaves, hasher="keccak256")  # warmup
    assert root == MerkleTree(leaves).root  # correctness anchor
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        root = merkle_root(dev_leaves, hasher="keccak256")
        times.append(time.perf_counter() - t0)
    dev_ms = min(times) * 1000.0

    # CPU baseline: native C keccak sequential width-16 merkle, x cores
    hash_fn = native_bind.keccak256 if native_bind.load() else keccak256
    t0 = time.perf_counter()
    level = [bytes(leaves[i]) for i in range(n)]
    while len(level) > 1:
        level = [
            hash_fn(b"".join(level[g : g + 16])) for g in range(0, len(level), 16)
        ]
    cpu_ms = (time.perf_counter() - t0) * 1000.0 / (os.cpu_count() or 1)
    _emit(M_MERKLE[0], dev_ms, M_MERKLE[1], cpu_ms / dev_ms)


def bench_flood() -> None:
    """Flood a FOUR-NODE PBFT chain (BASELINE config #4: "4-node Air chain,
    PBFT, txpool flooded with parallel-transfer txs") — all four engines in
    one process over the in-proc gateway (the reference's PBFTFixture
    pattern), so the measured TPS pays admission on the receiving node,
    payload gossip, the full three-phase consensus, REPLICA re-execution
    and verification on every node, and the 2PC commit x4.  A solo chain
    would overstate TPS by skipping consensus + replication entirely."""
    from fisco_bcos_tpu.codec.abi import ABICodec
    from fisco_bcos_tpu.crypto.suite import ecdsa_suite
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from fisco_bcos_tpu.front import InprocGateway
    from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig
    from fisco_bcos_tpu.node import Node, NodeConfig
    from fisco_bcos_tpu.protocol.transaction import TransactionFactory

    suite = ecdsa_suite()
    codec = ABICodec(suite.hash)
    n = FLOOD_TXS
    block_cap = min(5000, max(1000, n))
    keypairs = [
        suite.signature_impl.generate_keypair(secret=0xF100D + i) for i in range(4)
    ]
    cons = [ConsensusNode(kp.pub, weight=1) for kp in keypairs]
    gw = InprocGateway(auto=True)
    nodes = []
    for kp in keypairs:
        cfg = NodeConfig(
            genesis=GenesisConfig(
                consensus_nodes=list(cons), tx_count_limit=block_cap
            )
        )
        node = Node(cfg, keypair=kp)
        gw.connect(node.front)
        nodes.append(node)
    # ISSUE 14: with >1 core the flood runs the OVERLAPPED pipeline —
    # consensus messages on each engine's worker, 2PCs on the commit
    # workers, lazy roots resolving at quorum time. On a 1-core host the
    # worker threads can only time-slice one core (measured ~20% pure
    # GIL/queue tax, nothing to overlap INTO), so the drive defaults to
    # inline there — same pipeline semantics (lazy roots, zero-copy,
    # prebuild), minus thread thrash. FISCO_BENCH_FLOOD_WORKERS=0|1
    # overrides the auto-detection either way.
    workers_default = "1" if (os.cpu_count() or 1) > 1 else "0"
    if os.environ.get("FISCO_BENCH_FLOOD_WORKERS", workers_default) != "0":
        for node in nodes:
            node.engine.start_worker()

    fac = TransactionFactory(suite)
    sender = suite.signature_impl.generate_keypair(secret=0xF200D)

    def make_txs(tag: str):
        return [
            fac.create_signed(
                sender,
                chain_id="chain0",
                group_id="group0",
                block_limit=500,
                nonce=f"flood-{tag}-{i}",
                to=DAG_TRANSFER_ADDRESS,
                input=codec.encode_call("userAdd(string,uint256)", f"u{tag}{i}", 1),
            )
            for i in range(n)
        ]

    def leader_for_next(height: int) -> "Node":
        idx = nodes[0].pbft_config.leader_index(height, 0)
        target = nodes[0].pbft_config.nodes[idx].node_id
        return next(nd for nd in nodes if nd.node_id == target)

    def optimistic_head() -> int:
        # the pipelined sealer chains on the engine's optimistic head
        # (commits still in flight on the worker) — the drive loop must
        # pick the next leader the same way or it would stall the overlap
        return max(nd.engine.consensus_head()[0] for nd in nodes)

    t_child = time.monotonic()
    child_budget = _child_budget_s()
    if os.environ.get("FISCO_BENCH_TELEMETRY"):
        # ISSUE 13: compile-ledger hooks must be live BEFORE the warm
        # (compile) round so cold compiles are measured, not inferred
        from fisco_bcos_tpu.observability.device import install_observatory

        install_observatory()

    def flood_round(txs, deadline: float | None = None):
        entry = nodes[0]
        results = entry.txpool.submit_batch(txs)
        rejected = sum(1 for r in results if r.status != 0)
        if rejected:
            raise GateFailure(f"{rejected}/{len(txs)} txs rejected at admission")
        # gossip payloads so whichever node leads can fill its proposals
        entry.tx_sync.maintain()
        # progress-based stall detection: with the overlapped pipeline a
        # False seal_and_submit is NORMAL (proposal in flight, prebuild
        # tick) — only a wall of no committed-height progress is a stall
        last_height, last_progress = optimistic_head(), time.monotonic()
        while entry.txpool.pending_count() > 0:
            # wall-clock cap, not tx count: a too-slow chain fails its gate
            # with a reason instead of being killed mid-round by the parent
            now = time.monotonic()
            if deadline is not None and now > deadline:
                raise GateFailure("flood stopped at wall-clock deadline")
            head = optimistic_head()
            if head != last_height:
                last_height, last_progress = head, now
            elif now - last_progress > 15.0:
                raise GateFailure(f"flood stalled at height {head}")
            leader = leader_for_next(head + 1)
            if not leader.sealer.seal_and_submit():
                time.sleep(0.002)  # votes/2PCs drain on the workers
        # the TPS window closes when the pipelined 2PCs land, not when
        # the pool empties — drain every node's commit worker, then wait
        # for replica convergence. All tail waits respect the child
        # deadline's remaining headroom: a wedged commit worker must fail
        # the gate with its reason, never end as a budget-killed child.
        hard_stop = deadline + 8.0 if deadline is not None else None

        def tail_budget(cap: float) -> float:
            if hard_stop is None:
                return cap
            return max(0.5, min(cap, hard_stop - time.monotonic()))

        for nd in nodes:
            if not nd.scheduler.drain_commits(tail_budget(30.0)):
                raise GateFailure("commit worker failed to drain")
        tip = nodes[0].block_number()
        t_conv = time.monotonic() + tail_budget(15.0)
        while (
            any(nd.block_number() < tip for nd in nodes)
            and time.monotonic() < t_conv
        ):
            time.sleep(0.002)

    # round 1 warms every device program on the block path (admission batch
    # shapes, tx/receipt merkle, state root) on ALL FOUR nodes — a
    # production node compiles once per shape for its whole lifetime, so
    # steady-state TPS is the meaningful number; round 2 is the measured
    # one. Client-side signing happens outside the timed window (the
    # reference's flood helper likewise pre-builds txs —
    # DuplicateTransactionFactory.cpp).
    # the warm (compile) round may take at most 65% of the child budget so a
    # measured window always remains
    warm_deadline = (
        t_child + 0.65 * child_budget if child_budget is not None else None
    )
    flood_round(make_txs("w"), deadline=warm_deadline)
    backlog = nodes[0].txpool.pending_count()
    if backlog:  # would inflate TPS
        raise GateFailure(f"warm round left {backlog} txs pending")
    heights = {nd.block_number() for nd in nodes}
    if len(heights) != 1:
        raise GateFailure(
            f"nodes diverged after warm round: heights {sorted(heights)}"
        )
    measured_txs = make_txs("m")
    before = nodes[0].ledger.total_transaction_count()
    measure_deadline = (
        t_child + child_budget - 10 if child_budget is not None else None
    )
    # ISSUE 9: the 100 Hz sampling profiler rides the MEASURED round under
    # --telemetry, so the round artifact carries where the interpreter
    # actually spent the flood window; its duty cycle (sample cost /
    # wall) is the honest on/off overhead bound on this 1-core host
    prof = None
    warm_ledger = None
    alloc_window = None
    # measured-window boundary (EVERY round since ISSUE 14, not only under
    # --telemetry): drop the warm/compile round's tx index and stage
    # totals so the round artifact's per-stage vector covers ONLY the
    # measured flood — otherwise round-over-round check_perf diffs would
    # be dominated by cold-vs-warm compile variance.
    from fisco_bcos_tpu.observability import critical_path
    from fisco_bcos_tpu.observability.pipeline import PIPELINE
    from fisco_bcos_tpu.observability.storagelog import STORAGE

    critical_path.clear_indexes()
    PIPELINE.reset()
    # ISSUE 19: the storage observatory's codec/copy ledger likewise
    # covers ONLY the measured window (warm-round compile churn would
    # otherwise dominate the round-over-round codec-bytes diff)
    STORAGE.reset()
    prev_round_doc = _load_flood_artifact()
    if os.environ.get("FISCO_BENCH_TELEMETRY"):
        from fisco_bcos_tpu.observability.device import LEDGER
        from fisco_bcos_tpu.observability.profiler import SamplingProfiler

        # the warm round's compile ledger is kept for the device artifact
        # (it is where the cold compiles live by design), then reset so
        # the measured window's per-op phase vector is compile-clean
        warm_ledger = {
            "ledger": LEDGER.snapshot(),
            "op_phase_ms": LEDGER.phase_totals(),
        }
        LEDGER.reset()
        prof = SamplingProfiler(hz=100.0)
        prof.start()
        if STORAGE.enabled:
            # ISSUE 19: the tracemalloc window rides the profiler cadence
            # — same measured round, same on/off overhead accounting
            from fisco_bcos_tpu.observability.storagelog import (
                AllocationWindow,
            )

            alloc_window = AllocationWindow().start()
    t0 = time.perf_counter()
    flood_round(measured_txs, deadline=measure_deadline)
    dt = time.perf_counter() - t0
    if prof is not None:
        prof.stop()
    alloc_top = alloc_window.top(15) if alloc_window is not None else None
    committed = nodes[0].ledger.total_transaction_count() - before
    if committed < n:
        raise GateFailure(f"only {committed}/{n} txs committed")
    # every replica must hold the same chain the TPS number claims
    tips = {nd.block_number() for nd in nodes}
    roots = {
        nd.ledger.header_by_number(nd.block_number()).state_root for nd in nodes
    }
    if len(tips) != 1 or len(roots) != 1:
        raise GateFailure("replicas diverged during measured round")
    tps = committed / dt
    # recompile counts ride along so the next BENCH round can attribute the
    # e2e gap: with the plane on, a ragged flood must stay within the bucket
    # ladder instead of compiling one program per batch size
    from fisco_bcos_tpu.device.plane import get_plane
    from fisco_bcos_tpu.observability.device import compile_counts

    print(
        "# flood device compiles per op (distinct bucketed shapes): "
        + json.dumps(compile_counts()),
        flush=True,
    )
    _emit(M_FLOOD[0], tps, M_FLOOD[1], tps / 10_000.0)  # vs README.md:10
    if prof is not None:
        _dump_pipeline_artifact("flood", tps, prof, dt)
        _dump_device_artifact("flood", dt, warm_ledger)
    else:
        # ISSUE 14: the per-stage self-time flood artifact is written
        # EVERY round so check_perf can diff consecutive rounds even
        # when --telemetry is off (no profiler fold in this shape)
        _dump_flood_round_artifact(tps, dt)
    # ISSUE 16: the fleet observatory's per-phase round spans + quorum-edge
    # skew, written every round next to the pipeline artifact (noop and
    # placeholder-emitting when FISCO_FLEET_OBS=0)
    _dump_flood_rounds_artifact(nodes, dt)
    # ISSUE 19: the storage observatory's commit-path ledger — codec
    # bytes/block, copy-amplification, per-shard 2PC p95, top alloc sites
    # (noop and placeholder-emitting with FISCO_STORAGE_OBS=0)
    _dump_storage_artifact(dt, alloc_top)
    _gate_flood_round(prev_round_doc, tps)
    plane = get_plane()
    plane.drain(10.0)
    ratio = plane.coalesce_ratio()
    print(
        f"# device plane: {plane.stats()} wait_p99_ms="
        f"{plane.wait_p99_ms():.2f}",
        flush=True,
    )
    _emit(M_COALESCE[0], ratio, M_COALESCE[1], ratio)


def bench_scenario(name: str) -> None:
    """--scenario child: run a named scenario-lab workload on a live chain
    and emit a per-group TPS/latency breakdown (fisco_bcos_tpu/scenario/).

    Two artifact surfaces: JSON metric lines (one per group, plus the
    isolation ratio when applicable) and the full runner document written
    next to the bench output as ``bench_scenario.<name>.json`` — the
    per-group breakdown, quota/demotion snapshot, health registry, fault
    counts and the determinism digest for the seed."""
    from fisco_bcos_tpu.scenario import (
        ScenarioRunner,
        run_big_committee_bench,
        run_byzantine_bench,
        run_isolation_bench,
        run_proof_storm_bench,
    )

    seed = int(os.environ.get("FISCO_SCENARIO_SEED", "0") or 0)
    scale = float(os.environ.get("FISCO_SCENARIO_SCALE", "1") or 1)
    budget = _child_budget_s()
    deadline = max(budget - 20, 30) if budget is not None else None
    if name == "big-committee":
        doc = run_big_committee_bench(seed=seed, scale=scale, deadline_s=deadline)
        err = doc.get("error")
        ratio = doc["qc_bytes_ratio_64_vs_4"]
        # acceptance: committed-QC bytes constant in committee size —
        # n=64 within 1.1x of n=4 (vs_baseline >= 1.0 passes)
        _emit(
            "scenario_big_committee_qc_bytes_ratio", ratio, "x-n4",
            (1.1 / ratio) if ratio > 0 else 0.0, error=err,
        )
        speedup = doc["aggregate_speedup_vs_sequential_n64"]
        # acceptance: one aggregate verification beats n=64 sequential
        # per-vote verifies
        _emit(
            "scenario_big_committee_agg_speedup_n64", speedup, "x-sequential",
            speedup / 1.0, error=err,
        )
        _emit(
            "scenario_big_committee_verify_ms_n64",
            doc["committees"]["64"]["verify_ms_p50"], "ms",
            1.0 if not err else 0.0, error=err,
        )
        print(
            f"# big-committee: qc_bytes n4={doc['committees']['4']['qc_bytes']} "
            f"n64={doc['committees']['64']['qc_bytes']} (ratio {ratio}x), "
            f"verify_ms ratio {doc['verify_ms_ratio_64_vs_4']}x, "
            f"agg speedup {speedup}x vs sequential, "
            f"ed25519 bytes {doc['ed25519']}, "
            f"chain={doc.get('chain', {})}",
            flush=True,
        )
        group_docs = {}
    elif name == "byzantine":
        doc = run_byzantine_bench(seed=seed, scale=scale, deadline_s=deadline)
        err = doc.get("error")
        ratio = doc["liveness_ratio"]
        # acceptance: honest commit throughput with one byzantine replica
        # running the full attack catalog holds >= 0.5x the clean flood
        # (vs_baseline >= 1.0 passes)
        _emit(
            "scenario_byzantine_liveness_ratio", ratio, "x-clean",
            ratio / 0.5, error=err,
        )
        detected = sum(1 for r in doc["attacks"] if r["detected"])
        _emit(
            "scenario_byzantine_attacks_detected", detected, "attack",
            1.0 if doc["all_detected"] else 0.0,
            error=err
            or (None if doc["all_detected"] else "undetected or unrun attacks"),
        )
        # safety is binary: both legs' auditor reports must be clean AND
        # the adversary must land in the penalty box
        safe = (
            doc["audit_clean"]["ok"]
            and doc["audit_byzantine"]["ok"]
            and doc["adversary_demoted"]
        )
        _emit(
            "scenario_byzantine_audit_ok", 1.0 if safe else 0.0, "bool",
            1.0 if safe else 0.0,
            error=err
            or (
                None
                if safe
                else "chain-safety audit violations or adversary not demoted"
            ),
        )
        print(
            f"# byzantine: clean {doc['clean_tps']} tx/s vs attacked "
            f"{doc['byzantine_tps']} tx/s (liveness {ratio}x), "
            f"{detected}/{len(doc['attacks'])} attacks detected, "
            f"demoted={doc['adversary_demoted']}, "
            f"evidence={doc['evidence_counts']}, audit ok={safe}",
            flush=True,
        )
        group_docs = {}
    elif name == "byzantine-wire":
        from fisco_bcos_tpu.scenario import run_wire_bench

        doc = run_wire_bench(seed=seed, scale=scale, deadline_s=deadline)
        err = doc.get("error")
        ratio = doc["liveness_ratio"]
        # acceptance: same 0.5x liveness floor as the in-proc catalog, but
        # measured over real TCP sockets (connect/flood/redial included)
        _emit(
            "scenario_byzantine_wire_liveness_ratio", ratio, "x-clean",
            ratio / 0.5, error=err,
        )
        detected = sum(1 for r in doc.get("attacks", ()) if r["detected"])
        _emit(
            "scenario_byzantine_wire_attacks_detected", detected, "attack",
            1.0 if doc["all_detected"] else 0.0,
            error=err
            or (None if doc["all_detected"] else "undetected or unrun attacks"),
        )
        # committee-wide demotion: every honest node confirmed the
        # offender via gossiped evidence, within this many settle rounds
        rounds = doc["convergence_rounds_max"]
        _emit(
            "scenario_byzantine_wire_convergence_rounds", rounds, "round",
            1.0 if doc["gossip_converged"] else 0.0,
            error=err
            or (None if doc["gossip_converged"] else "gossip never converged"),
        )
        safe = (
            doc.get("audit_clean", {}).get("ok", False)
            and doc.get("audit_byzantine", {}).get("ok", False)
            and doc["adversary_demoted"]
        )
        _emit(
            "scenario_byzantine_wire_audit_ok", 1.0 if safe else 0.0, "bool",
            1.0 if safe else 0.0,
            error=err
            or (
                None
                if safe
                else "chain-safety audit violations or adversary not demoted"
            ),
        )
        print(
            f"# byzantine-wire: clean {doc['clean_tps']} tx/s vs attacked "
            f"{doc['byzantine_tps']} tx/s (liveness {ratio}x), "
            f"{detected} attacks detected, gossip converged="
            f"{doc['gossip_converged']} (rounds<={rounds}), "
            f"demoted={doc['adversary_demoted']}, audit ok={safe}",
            flush=True,
        )
        group_docs = {}
    elif name == "proof-storm":
        doc = run_proof_storm_bench(seed=seed, scale=scale, deadline_s=deadline)
        err = doc.get("error")
        speedup = doc["speedup_vs_direct"]
        # acceptance: >= 50x proofs/sec over the direct per-request
        # Ledger.tx_proof rebuild at 10^5 queued clients
        _emit(
            "scenario_proof_storm_proofs_per_s", doc["proofs_per_s"], "proof/s",
            speedup / 50.0, error=err,
        )
        _emit(
            "scenario_proof_storm_cache_hit_ratio", doc["cache_hit_ratio"],
            "ratio", doc["cache_hit_ratio"] / 0.9, error=err,
        )
        # the write path must keep >= 0.7x its solo TPS under the storm
        ratio = doc["flood"]["ratio"]
        _emit(
            "scenario_proof_storm_flood_tps_ratio", ratio, "x-solo",
            ratio / 0.7, error=err,
        )
        # ISSUE 18 succinct lanes: state membership proofs/sec off the
        # StatePlane snapshot (zero tolerated verify failures) and the
        # headers/sec of ONE aggregate multi-pairing admission vs the old
        # per-header pairing loop (>= 1x acceptance: aggregation must not
        # cost more than the loop it replaces)
        state = doc.get("state_proofs") or {}
        if state.get("proofs_served"):
            _emit(
                "scenario_proof_storm_state_proofs_per_s",
                state["proofs_per_s"], "proof/s",
                0.0 if state["verify_failures"] else 1.0, error=err,
            )
        sync = doc.get("header_sync") or {}
        if sync.get("headers_per_s"):
            _emit(
                "scenario_proof_storm_sync_headers_per_s",
                sync["headers_per_s"], "header/s",
                sync["speedup_vs_per_header"], error=err,
            )
        print(
            f"# proof-storm: {doc['proofs_served']} proofs to "
            f"{doc['queued_clients']} queued clients, "
            f"p95={doc['proof_batch_latency_ms_p95']}ms/batch, "
            f"steady {doc['proofs_per_s_steady']}/s vs direct "
            f"{doc['direct_baseline_proofs_per_s']}/s (speedup {speedup}x), "
            f"verify_failures={doc['verify_failures']}, "
            f"state {state.get('proofs_per_s', 0)}/s over "
            f"{state.get('committed_keys', 0)} keys, header sync "
            f"{sync.get('headers_per_s', 0)}/s aggregate "
            f"({sync.get('speedup_vs_per_header', 0)}x vs per-header)",
            flush=True,
        )
        group_docs = {}
    elif name == "isolation":
        doc = run_isolation_bench(seed=seed, scale=scale, deadline_s=deadline)
        ratio = doc["victim_ratio"]
        err = doc.get("error") or doc["combined"].get("error")
        # acceptance: victim keeps >= 0.7x of its solo TPS while the abuser
        # floods — vs_baseline is measured/required so >= 1.0 passes
        _emit(
            "scenario_isolation_victim_tps_ratio", ratio, "x-solo",
            ratio / 0.7, error=err,
        )
        # only the ABUSER group's shed counts as proof: the victim's own
        # quota drops (or solo-leg residue) passing the gate would claim
        # isolation that never happened
        abuser = doc["abuser_group"]
        shed = sum(
            v
            for k, v in doc["abuse_shed_counters"].items()
            if f'group="{abuser}"' in k
        )
        _emit(
            "scenario_isolation_abuse_shed_txs", shed, "tx",
            1.0 if shed > 0 else 0.0,
            error=None if shed > 0 else "no abuser traffic shed at admission",
        )
        group_docs = {
            **doc["combined"]["groups"],
            "solo:" + doc["victim_group"]: doc["solo"]["groups"][
                doc["victim_group"]
            ],
        }
    else:
        doc = ScenarioRunner(
            name, seed=seed, scale=scale, deadline_s=deadline
        ).run()
        group_docs = doc["groups"]
    for g, gd in sorted(group_docs.items()):
        label = g.replace(":", "_")
        _emit(
            f"scenario_{name}_{label}_tps", gd["tps"], "tx/s", 0.0,
            error=doc.get("error"),
        )
        print(
            f"# scenario {name} group={g} submitted={gd['submitted']} "
            f"admitted={gd['admitted']} committed={gd['committed']} "
            f"rejected={gd['rejected']} p50={gd['latency_ms_p50']}ms "
            f"p95={gd['latency_ms_p95']}ms",
            flush=True,
        )
    base = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(base, f"bench_scenario.{name}.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)
    print(
        f"# scenario artifact -> {path} (seed={seed}, digest="
        f"{doc.get('determinism_digest', doc.get('combined', {}).get('determinism_digest', ''))[:16]})",
        flush=True,
    )


def _flood_artifact_path() -> str:
    base = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(base, "bench_telemetry.flood.pipeline.json")


def _load_flood_artifact() -> dict | None:
    """Previous round's flood artifact (None on first round / bad file)."""
    try:
        with open(_flood_artifact_path()) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _flood_round_doc(tag: str, tps: float, window_s: float) -> dict:
    """The round-artifact base document — everything check_perf diffs
    (flood TPS, per-stage self-time vector, /pipeline snapshot). Single-
    sourced so the --telemetry writer (which adds the profiler fold) and
    the every-round writer stay key-compatible across rounds."""
    from fisco_bcos_tpu.observability import critical_path
    from fisco_bcos_tpu.observability.pipeline import PIPELINE, pipeline_doc

    PIPELINE.sample_once()  # final watermark sweep before the snapshot
    agg = critical_path.aggregate_stage_self_ms()
    return {
        "tag": tag,
        "flood_tps": round(tps, 2),
        "window_s": round(window_s, 3),
        "stage_self_ms": {
            name: v["self_ms"] for name, v in agg["stages"].items()
        },
        "stage_agg": agg,
        "pipeline": pipeline_doc(),
    }


def _dump_flood_round_artifact(tps: float, window_s: float) -> None:
    """The --telemetry-less round artifact (ISSUE 14): the base doc,
    without the profiler fold."""
    doc = _flood_round_doc("flood", tps, window_s)
    path = _flood_artifact_path()
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)
    print(f"# flood round artifact -> {path}", flush=True)


def _flood_rounds_artifact_path() -> str:
    base = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(base, "bench_telemetry.flood.rounds.json")


def _dump_flood_rounds_artifact(nodes, window_s: float) -> None:
    """ISSUE 16 round artifact: the fleet observatory's view of the
    measured flood — per-consensus-phase span vector aggregated across
    every aligned round on every replica (``round_phase_ms``, the p95 per
    phase — what tool/check_perf.py diffs round over round), the
    inter-node skew percentiles of the quorum edge, and any straggler
    attributions. Also emits ``fleet_round_skew_ms_p95`` as a metric
    line. With FISCO_FLEET_OBS=0 the ledgers recorded nothing: emit the
    disabled placeholder and write no artifact (the switch must stay a
    no-op on the flood path)."""
    svc = getattr(nodes[0], "fleet", None)
    if svc is None:
        _emit(
            M_ROUND_SKEW[0], 0.0, M_ROUND_SKEW[1], 0.0,
            error="fleet observatory disabled (FISCO_FLEET_OBS=0)",
        )
        return
    from fisco_bcos_tpu.observability.roundlog import rounds_doc

    # pull every replica's ledger over the wire and align with
    # record_skew=True — the flood bench is an owning aggregation path
    # (like /fleet), so the round skews land in fisco_round_skew_ms too
    ledgers, offsets = svc._peer_ledgers({"last": 64})
    rounds = rounds_doc(ledgers, offsets, last=64, record_skew=True)
    phase_samples: dict[str, list[float]] = {}
    stragglers: dict[str, int] = {}
    for rd in rounds["rounds"]:
        for per_node in rd["nodes"].values():
            for phase, ms in per_node["phases"].items():
                phase_samples.setdefault(phase, []).append(ms)
        if "straggler" in rd:
            key = str(rd["straggler"])
            stragglers[key] = stragglers.get(key, 0) + 1
    from fisco_bcos_tpu.observability.roundlog import percentile

    doc = {
        "tag": "flood",
        "window_s": round(window_s, 3),
        "rounds_aligned": len(rounds["rounds"]),
        "nodes": rounds["nodes"],
        "round_phase_ms": {
            phase: round(percentile(v, 95), 3)
            for phase, v in sorted(phase_samples.items())
        },
        "round_phase_detail": {
            phase: {
                "n": len(v),
                "p50": round(percentile(v, 50), 3),
                "p95": round(percentile(v, 95), 3),
                "max": round(max(v), 3),
            }
            for phase, v in sorted(phase_samples.items())
        },
        "skew_ms": rounds["skew_ms"],
        "stragglers": stragglers,
        "view_changes": rounds["view_changes"],
    }
    path = _flood_rounds_artifact_path()
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)
    p95 = rounds["skew_ms"]["p95"]
    # acceptance: the corrected quorum edge across an in-proc fleet must
    # stay under the skew budget — vs_baseline >= 1.0 passes
    budget_ms = 250.0
    _emit(
        M_ROUND_SKEW[0], p95, M_ROUND_SKEW[1],
        budget_ms / max(p95, 1e-6),
        error=None if p95 < budget_ms
        else f"round skew p95 >= {budget_ms:.0f} ms",
    )
    print(
        f"# fleet rounds: aligned={doc['rounds_aligned']} "
        f"skew_p95={p95:.2f}ms stragglers={stragglers or '{}'} -> {path}",
        flush=True,
    )


def _storage_artifact_path() -> str:
    base = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(base, "bench_telemetry.flood.storage.json")


def _dump_storage_artifact(window_s: float, alloc_top=None) -> None:
    """ISSUE 19 storage artifact: the storage observatory's view of the
    measured flood — commit-path codec bytes per block, the
    copy-amplification ratio (entries copied per durably-written row),
    per-shard 2PC prepare/commit p95, and (under --telemetry) the top
    tracemalloc allocation sites attributed to pipeline stages.
    ``storage_commit`` is the vector tool/check_perf.py diffs round over
    round (20%-relative + 5.0 absolute-floor gates). With
    FISCO_STORAGE_OBS=0 the recorder saw nothing: emit the disabled
    placeholder and write no artifact (the switch must stay a no-op on
    the flood path)."""
    from fisco_bcos_tpu.observability.roundlog import percentile
    from fisco_bcos_tpu.observability.storagelog import STORAGE

    if not STORAGE.enabled:
        _emit(
            M_STORAGE_AMP[0], 0.0, M_STORAGE_AMP[1], 0.0,
            error="storage observatory disabled (FISCO_STORAGE_OBS=0)",
        )
        return
    snap = STORAGE.snapshot(last_blocks=128)
    blocks = [b for b in snap["blocks"] if not b.get("aborted")]
    n_blocks = max(len(blocks), 1)
    bytes_per_block = sum(b["bytes_encoded"] for b in blocks) / n_blocks
    copies_per_block = sum(b["entries_copied"] for b in blocks) / n_blocks
    rows_per_block = sum(b["rows_written"] for b in blocks) / n_blocks
    amp = snap["totals"]["copy_amplification_mean"]
    shard_prep = [
        ops["prepare"]["p95_ms"]
        for ops in snap["shards"].values()
        if "prepare" in ops
    ]
    shard_comm = [
        ops["commit"]["p95_ms"]
        for ops in snap["shards"].values()
        if "commit" in ops
    ]
    doc = {
        "tag": "flood",
        "window_s": round(window_s, 3),
        "blocks_measured": len(blocks),
        # the check_perf round-over-round vector — codec bytes/block sits
        # in the thousands so a +30% regression clears the 5.0 floor
        "storage_commit": {
            "codec_bytes_per_block": round(bytes_per_block, 1),
            "entries_copied_per_block": round(copies_per_block, 1),
            "shard_prepare_p95_ms": (
                round(percentile(shard_prep, 95), 3) if shard_prep else 0.0
            ),
            "shard_commit_p95_ms": (
                round(percentile(shard_comm, 95), 3) if shard_comm else 0.0
            ),
        },
        "rows_written_per_block": round(rows_per_block, 1),
        "copy_amplification": amp,
        "codec": snap["codec"],
        "copies": snap["copies"],
        "pages_rewritten": snap["pages_rewritten"],
        "shards": snap["shards"],
        "totals": snap["totals"],
        "blocks": blocks[-16:],
    }
    if alloc_top is not None:
        doc["alloc_top"] = alloc_top
    path = _storage_artifact_path()
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)
    _emit(M_STORAGE_AMP[0], amp, M_STORAGE_AMP[1], amp)
    top3 = ", ".join(
        f"{a['site']}={a['kib']:.0f}KiB" for a in (alloc_top or [])[:3]
    )
    print(
        f"# storage ledger: blocks={len(blocks)} "
        f"codec_bytes/block={bytes_per_block:.0f} amp={amp:.2f} "
        + (f"alloc_top=[{top3}] " if top3 else "")
        + f"-> {path}",
        flush=True,
    )


def _gate_flood_round(prev_doc: dict | None, tps: float) -> None:
    """Consecutive-round flood-TPS regression gate (ISSUE 14): diff this
    round's TPS against the previous round's artifact with the
    tool/check_perf differ (>= 20% drop fails the metric line)."""
    prev_tps = (prev_doc or {}).get("flood_tps")
    if not prev_tps:
        return
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tool"))
    import check_perf

    regressions, _notes = check_perf.diff(
        {"flood_tps": prev_tps}, {"flood_tps": tps}
    )
    ratio = tps / prev_tps
    _emit(
        "flood_tps_vs_prev_round",
        ratio,
        "x",
        ratio / 0.8,  # the 20% check_perf gate expressed as measured/required
        error="; ".join(regressions) if regressions else None,
    )


def _dump_pipeline_artifact(tag: str, tps: float, prof, window_s: float) -> None:
    """ISSUE 9 round artifact: per-stage utilization + blocked-on edges
    (the pipeline observatory snapshot), the per-stage self-time vector
    aggregated across ALL sampled txs in the flood window (what
    tool/check_perf.py diffs round over round), and the 100 Hz profiler's
    self-time/flamegraph fold with its measured duty-cycle overhead."""
    report = prof.report()
    doc = _flood_round_doc(tag, tps, window_s)
    doc["profile"] = report
    base = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(base, f"bench_telemetry.{tag}.pipeline.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)
    overhead_pct = report["overhead"]["duty_cycle"] * 100.0
    # acceptance: the 100 Hz profiler must cost < 5% flood TPS —
    # vs_baseline is allowed/measured so >= 1.0 passes
    _emit(
        "flood_profiler_overhead_pct",
        overhead_pct,
        "%",
        5.0 / max(overhead_pct, 1e-6),
        error=None if overhead_pct < 5.0 else "profiler duty cycle >= 5%",
    )
    stages = doc["pipeline"]["stages"]  # the SAME snapshot the artifact holds
    busiest = max(
        stages.items(), key=lambda kv: kv[1]["busy_ms"], default=(None, None)
    )[0]
    edges = sorted(
        (
            (s, on, ms)
            for s, v in stages.items()
            for on, ms in v["blocked_ms"].items()
        ),
        key=lambda e: -e[2],
    )
    top_edge = f"{edges[0][0]} blocked_on={edges[0][1]} {edges[0][2]:.0f}ms" \
        if edges else "none"
    print(
        f"# pipeline: busiest={busiest} top_blocked=[{top_edge}] "
        f"profiler_samples={report['samples']} "
        f"overhead={overhead_pct:.2f}% -> {path}",
        flush=True,
    )


def _dump_device_artifact(tag: str, window_s: float, warm_ledger) -> None:
    """ISSUE 13 round artifact: the device observatory's view of the
    MEASURED flood window — per-op queue/compile/transfer/execute phase
    vector (what tool/check_perf.py diffs round over round, execute-phase
    per op), the measured compile ledger (ideally compile-free: the warm
    round paid the compiles, kept under ``warm_round``), storm state, and
    the observatory's own measured bookkeeping overhead (< 5% of flood
    wall is the acceptance bound)."""
    from fisco_bcos_tpu.observability.device import LEDGER, compile_counts

    rows = LEDGER.snapshot()
    doc = {
        "tag": tag,
        "window_s": round(window_s, 3),
        "op_phase_ms": LEDGER.phase_totals(),
        "ledger": rows,
        "cold_compiles": sum(r["cold_compiles"] for r in rows),
        "cache_hits": sum(r["cache_hits"] for r in rows),
        "compile_counts": compile_counts(),
        "storm": LEDGER.storm_state(),
        "obs_overhead_s": round(LEDGER.overhead_seconds(), 6),
        "adjacency": LEDGER.adjacency(),
        "warm_round": warm_ledger,
    }
    base = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(base, f"bench_telemetry.{tag}.device.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)
    overhead_pct = doc["obs_overhead_s"] / max(window_s, 1e-9) * 100.0
    # acceptance: the device observatory must cost < 5% of flood wall —
    # vs_baseline is allowed/measured so >= 1.0 passes
    _emit(
        "flood_device_obs_overhead_pct",
        overhead_pct,
        "%",
        5.0 / max(overhead_pct, 1e-6),
        error=None if overhead_pct < 5.0 else "device observatory >= 5%",
    )
    # measured phases only (observability/device.py): `sync` is the host
    # waiting for the device's result
    syncs = {
        op: phases.get("sync", 0.0)
        for op, phases in doc["op_phase_ms"].items()
    }
    top = max(syncs.items(), key=lambda kv: kv[1], default=(None, 0.0))
    print(
        f"# device: {doc['cold_compiles']} cold compile(s) in the measured "
        f"window, {doc['cache_hits']} cache load(s), top sync "
        f"op={top[0]} ({top[1]:.0f}ms) -> {path}",
        flush=True,
    )


def _dump_telemetry(tag: str) -> None:
    """--telemetry mode: write the metrics snapshot + trace next to the
    bench JSON lines (per-child files — each --only child is its own
    process), so every perf claim ships an inspectable artifact (load the
    trace in ui.perfetto.dev)."""
    if not os.environ.get("FISCO_BENCH_TELEMETRY"):
        return
    from fisco_bcos_tpu.observability import TRACER
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    base = os.path.dirname(os.path.abspath(__file__))
    mpath = os.path.join(base, f"bench_telemetry.{tag}.metrics.txt")
    tpath = os.path.join(base, f"bench_telemetry.{tag}.trace.json")
    with open(mpath, "w") as f:
        # artifact file, not a scrape: include the OpenMetrics exemplars
        f.write(REGISTRY.render(openmetrics=True))
    with open(tpath, "w") as f:
        f.write(TRACER.export_json())
    print(f"# telemetry metrics={mpath} trace={tpath}", flush=True)
    # per-tx critical path: stitch the last committed tx's lifecycle into
    # an ordered stage breakdown with the dominant stage named — the
    # attributable-latency artifact every perf claim should ship
    from fisco_bcos_tpu.observability import critical_path

    tx = critical_path.latest_committed_tx()
    if tx is not None:
        cpath = os.path.join(base, f"bench_telemetry.{tag}.critical_path.json")
        doc = critical_path.trace_tx(tx)
        with open(cpath, "w") as f:
            json.dump(doc, f, indent=1, default=str)
        print(
            f"# critical path tx={tx[:16]} dominant={doc.get('dominant')} "
            f"({doc.get('dominant_ms')}ms of {doc.get('total_ms')}ms) "
            f"-> {cpath}",
            flush=True,
        )


def bench_storage_child() -> None:
    """--only storage child (ISSUE 19): the bench_storage.py backend legs
    on the round cadence. Rides the parent's budget/deadline split like
    the scenario children — the leg loop stops at the deadline (a slow
    disk must yield degraded lines, never a budget-killed child) — and
    writes the per-(backend, op) rows/s vector to ``bench_storage.json``
    next to the metric lines."""
    import bench_storage

    budget = _child_budget_s()
    deadline = (
        time.monotonic() + max(budget - 15, 20)
        if budget is not None
        else None
    )
    n = int(os.environ.get("FISCO_BENCH_STORAGE_ROWS", "20000") or 20000)
    if budget is not None and budget < 60:
        # a thin slice measures fewer rows instead of risking the kill
        n = min(n, 5000)
    results = bench_storage.run(n, deadline=deadline)
    doc = {
        "n_rows": n,
        "budget_s": budget,
        "results": results,
        "rows_per_s": {
            f"{r['backend']}_{r['op']}": r["value"] for r in results
        },
    }
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_storage.json"
    )
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)
    print(f"# storage bench artifact -> {path}", flush=True)


def _child_budget_s() -> float | None:
    """Wall-clock budget handed to this --only child by the parent's
    deadline scheduler (None when run standalone)."""
    raw = os.environ.get("FISCO_BENCH_CHILD_BUDGET")
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        return None


def main() -> None:
    # The WHOLE bench must fit one driver budget: r4's artifact lost its
    # flood metric to the driver's `timeout` (rc=124) because per-metric
    # caps summed far beyond it. A deadline scheduler splits one explicit
    # total across the children — each child gets remaining/remaining_count,
    # so cheap children donate surplus to later ones and the final child
    # still ends before the total. Default must be conservative enough for
    # an unknown driver budget.
    import subprocess
    import sys

    t_start = time.monotonic()
    try:
        total_s = float(os.environ.get("FISCO_BENCH_TOTAL_BUDGET", "1500"))
    except ValueError:
        total_s = 1500.0  # malformed env must not cost the artifact

    def _text(raw) -> str:
        if raw is None:
            return ""
        if isinstance(raw, bytes):  # kill can truncate mid-character
            return raw.decode(errors="replace")
        return raw

    rc = 0
    # each metric runs in its own killable subprocess, one after the other
    # (the chip belongs to one process at a time; this parent never touches
    # JAX): a hang inside native code, where no Python signal can fire,
    # costs one metric's slice, not the whole run
    # cheap-compile-first: the deadline split hands each child
    # remaining/remaining_count, so early finishers donate surplus to the
    # expensive EC children and the flood
    # (the storage child is pure host CPU — it runs second so its surplus
    # donates to the compile-heavy EC children and the flood)
    names = ["merkle", "storage", "admission", "sm2", "flood"]
    # ROADMAP frontier wired into the round cadence: the isolation
    # victim-ratio (>=0.7x acceptance) and the proof-storm read path are
    # tracked per round alongside flood TPS. FISCO_BENCH_SCENARIOS=0 opts
    # out; the children ride the same deadline split + kill machinery.
    if os.environ.get("FISCO_BENCH_SCENARIOS", "1") != "0":
        names += [
            "scenario:isolation",
            "scenario:proof-storm",
            "scenario:big-committee",
            "scenario:byzantine",
        ]
    for i, name in enumerate(names):
        remaining = total_s - (time.monotonic() - t_start) - 10  # emit reserve
        if remaining < 20:
            print(f"# bench budget exhausted before {name}", flush=True)
            break
        budget_s = remaining / (len(names) - i)
        out = err = ""
        res = None
        try:
            env = dict(
                os.environ, FISCO_BENCH_CHILD_BUDGET=str(int(budget_s))
            )
            res = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--only", name],
                timeout=budget_s + 15,  # grace: child self-caps first
                capture_output=True,
                env=env,
            )
            out, err = _text(res.stdout), _text(res.stderr)
            failed = bool(res.returncode)
        except subprocess.TimeoutExpired as e:
            out, err = _text(e.stdout), _text(e.stderr)
            print(f"# bench {name} timed out after {budget_s}s", flush=True)
            failed = True
        except Exception as e:  # exec failure etc. — artifact must survive
            print(f"# bench {name} could not run: {e}", flush=True)
            failed = True
        if failed:
            rc = 1
            for line in err.splitlines()[-4:]:  # surface the crash reason
                print(f"# {name} stderr: {line[:300]}", flush=True)
        for line in out.splitlines():
            if line.startswith("{") or line.startswith("#"):
                print(line, flush=True)
        if failed and res is not None and res.returncode == RC_NOT_ON_CHIP:
            # no chip: every later child would refuse the same way
            raise SystemExit(RC_NOT_ON_CHIP)
    # a metric whose child failed has no line: absent is "not measured"
    raise SystemExit(rc)  # a child crashed/timed out/failed its gate


def _main_only(name: str) -> None:
    fns = {
        "admission": bench_admission,
        "sm2": bench_sm2,
        "merkle": bench_merkle,
        "flood": bench_flood,
        "storage": bench_storage_child,
    }
    if name.startswith("scenario:"):
        scen = name.split(":", 1)[1]
        _init_jax()
        try:
            bench_scenario(scen)
            _dump_telemetry(f"scenario_{scen}")
        except Exception as e:
            print(f"# bench scenario {scen} failed: {e}", flush=True)
            raise SystemExit(1)
        return
    if name not in fns:
        print(f"# unknown bench '{name}'", flush=True)
        raise SystemExit(2)
    if name != "storage":
        # the storage child is pure host CPU and never touches JAX
        _init_jax()
    try:
        fns[name]()
        _dump_telemetry(name)
    except Exception as e:
        print(f"# bench bench_{name} failed: {type(e).__name__}: {e}", flush=True)
        raise SystemExit(1)


def _main_scenario(name: str) -> None:
    """--scenario parent: run one named scenario through the same killable
    --only child machinery as the metric benches (a wedged chain costs this
    run, not the caller's whole budget)."""
    import subprocess
    import sys

    from fisco_bcos_tpu.scenario import SCENARIOS

    if name not in SCENARIOS and name not in (
        "isolation", "proof-storm", "big-committee", "byzantine",
        "byzantine-wire",
    ):
        known = ", ".join(sorted(SCENARIOS))
        print(f"# unknown scenario '{name}' (known: {known})", flush=True)
        raise SystemExit(2)
    try:
        total_s = float(os.environ.get("FISCO_BENCH_TOTAL_BUDGET", "1200"))
    except ValueError:
        total_s = 1200.0
    child = f"scenario:{name}"
    env = dict(os.environ, FISCO_BENCH_CHILD_BUDGET=str(int(total_s - 20)))
    rc = 0
    out = err = ""
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--only", child],
            timeout=total_s + 15,
            capture_output=True,
            env=env,
        )
        out = res.stdout.decode(errors="replace")
        err = res.stderr.decode(errors="replace")
        rc = res.returncode
    except subprocess.TimeoutExpired as e:
        out = (e.stdout or b"").decode(errors="replace")
        err = (e.stderr or b"").decode(errors="replace")
        print(f"# scenario {name} timed out after {total_s}s", flush=True)
        rc = 1
    for line in out.splitlines():
        if line.startswith("{") or line.startswith("#"):
            print(line, flush=True)
    if rc:
        for line in err.splitlines()[-4:]:
            print(f"# scenario stderr: {line[:300]}", flush=True)
    raise SystemExit(rc)


if __name__ == "__main__":
    import sys as _sys

    if "--telemetry" in _sys.argv:
        # telemetry artifacts feed dashboards; refuse to produce them from
        # a tree whose enforced invariants regressed (or whose accepted-debt
        # baseline went stale) — `python -m fisco_bcos_tpu.analysis` first
        from fisco_bcos_tpu.analysis import check_repo as _check_repo

        _new, _stale = _check_repo()
        if _new or _stale:
            for _f in _new:
                print(f"# analysis: {_f.render()}", flush=True)
            for _k in _stale:
                print(f"# analysis: stale baseline entry: {_k}", flush=True)
            print(
                "# --telemetry refused: static-analysis baseline has "
                f"unreviewed regressions ({len(_new)} new finding(s), "
                f"{len(_stale)} stale entr(ies))",
                flush=True,
            )
            raise SystemExit(2)
        # same refusal for the jaxpr baseline: a dashboard artifact must
        # not be produced while the committed program fingerprints don't
        # cover the inventory. Fast path — one cheap program re-traced,
        # coverage/stale checked by NAME against the full inventory
        # (`--jaxpr` re-traces everything non-slow; too slow for here).
        from fisco_bcos_tpu.analysis import progaudit as _progaudit

        _jres = _progaudit.audit(
            programs=["fisco_bcos_tpu/ops/keccak.py:keccak256_blocks"]
        )
        _jdiff = _progaudit.diff_audit(
            _jres, _progaudit.load_jaxpr_baseline()
        )
        if not _jdiff["ok"]:
            for _c in _jdiff["changed"]:
                print(
                    f"# jaxpr: CHANGED {_c['key']}: {_c['explanation']}",
                    flush=True,
                )
            for _lbl in ("new", "stale", "missing", "missing_spec"):
                for _k in _jdiff[_lbl]:
                    print(f"# jaxpr: {_lbl}: {_k}", flush=True)
            for _f in _jdiff["failures"]:
                print(
                    f"# jaxpr: failure: {_f['key']}: {_f['error']}",
                    flush=True,
                )
            print(
                "# --telemetry refused: tool/jaxpr_baseline.json is stale "
                "vs the jit inventory (python -m fisco_bcos_tpu.analysis "
                "--jaxpr, then --update-jaxpr-baseline after review)",
                flush=True,
            )
            raise SystemExit(2)
        # dump the metrics snapshot + per-block trace alongside the JSON
        # lines (propagates to --only children through the environment)
        _sys.argv.remove("--telemetry")
        os.environ["FISCO_BENCH_TELEMETRY"] = "1"
    if "--seed" in _sys.argv:
        i = _sys.argv.index("--seed")
        if i + 1 >= len(_sys.argv):
            print("usage: bench.py --scenario <name> [--seed N]")
            raise SystemExit(2)
        os.environ["FISCO_SCENARIO_SEED"] = _sys.argv[i + 1]
        del _sys.argv[i : i + 2]
    if "--scenario" in _sys.argv:
        i = _sys.argv.index("--scenario")
        if i + 1 >= len(_sys.argv):
            print("usage: bench.py [--telemetry] --scenario <name> [--seed N]")
            raise SystemExit(2)
        _main_scenario(_sys.argv[i + 1])
    elif len(_sys.argv) >= 2 and _sys.argv[1] == "--only":
        if len(_sys.argv) < 3:
            print(
                "usage: bench.py [--telemetry] "
                "[--only admission|sm2|merkle|flood|storage|scenario:<name>] "
                "[--scenario <name> [--seed N]]"
            )
            raise SystemExit(2)
        _main_only(_sys.argv[2])
    else:
        main()

#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the chain still starts on the chip.

Drives the system's main path once through the entry points a user calls, on
one TPU, and checks what comes out by the repo's own means:

- ``air``    the served path: ``build_chain`` → the generated ``start.sh`` →
             a few hundred signed DagTransfer txs over JSON-RPC
             ``sendTransaction`` from a CPU-pinned SDK client, every
             acknowledged tx read back with ``getTransactionReceipt``; then
             ``/device`` ``/health`` ``/metrics`` and a clean SIGTERM.
- ``air4``   BASELINE config #4 in its one-chip mapping at the upstream block
             size: four in-process nodes over ``InprocGateway``,
             ``tx_count_limit=1000``, ≥ 5,000 signed txs from 64 senders in
             1,000-tx batches through ``txpool.submit_batch`` at the next
             leader, gossiped by ``tx_sync.maintain()``. Fused admission runs
             on the chip at the 1,024 bucket on the entry node and, through
             the sync lane, on the three replicas; the first batch, a batch
             with corrupted signatures, and one full-width 10,000-tx block
             (bucket 10,240) are compared lane for lane with the native
             engine.
- ``cache``  a fresh process repeats one ``air4`` block and the 10,240 call
             and must find every program in the persistent compile cache.

The parent is an orchestrator that never initialises a JAX backend (it does
not import the package at all): a chip belongs to one process at a time, so
each phase is a child that owns the chip alone and has exited before the next
starts. Chip children run with ``JAX_PLATFORMS=tpu`` — a failed TPU init
raises instead of falling to the CPU; ``build_chain`` and the RPC client run
with ``JAX_PLATFORMS=cpu``. ``JAX_COMPILATION_CACHE_DIR`` is left to the
environment (unset: ``<checkout>/.jax_cache``).

Reduced (stated again in the output): BASELINE config #4's 50,000 txs → 5,000;
admission compiled at the 1,024 and 10,240 buckets only (each bucket of the
~148k-equation program is a minutes-class compile).

Exit 0 and a last stdout line ``{"ok": true, "device": {...}}`` only when
every phase ran on ``tpu`` and passed. Without a TPU — or in a directory that
holds nothing of the repo but this file — it exits non-zero, names the reason
in one line and prints no result. Logs and the per-phase documents land in
``chiprun_out/chip_smoke/``.

``--rehearse`` walks the same phases at a tiny size on the CPU (forced device
admission, 32-lane bucket). A rehearsal says that it is one on every line,
never prints a result and never exits 0: exit 3 means "rehearsal complete,
every check that can hold on a CPU held".
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")

TIME_LIMIT_S = 1200.0  # the contract's limit, compilation included
RC_REHEARSAL_DONE = 3

# real sizes: upstream block size (tool/build_chain.py, ledger/ledger.py),
# BASELINE config #4 cut from 50,000 txs to 5,000, one 10,000-tx block
REAL = {
    "air_txs": 300, "block_txs": 1000, "blocks": 5, "senders": 64,
    "full_width": 10_000,
}
REHEARSAL = {
    "air_txs": 24, "block_txs": 32, "blocks": 3, "senders": 8,
    "full_width": 32,
}
REDUCED = (
    "BASELINE config #4 50,000 txs -> {txs}; admission compiled at two "
    "buckets only, the {b1}-tx block's and the {b2}-lane block's"
)

# programs the served path must have put on the device (ops/merkle.py routes
# tree and state-root hashing to the device on an accelerator backend)
AIR_DEVICE_OPS = ("keccak256", "merkle_root")


# ---------------------------------------------------------------------------
# Pass/fail predicates — pure functions over the documents the phases
# produce (the /device, /health and /metrics shapes), unit-tested on canned
# documents in tests/test_chip_smoke.py. Each returns the list of reasons the
# phase failed; empty means pass.
# ---------------------------------------------------------------------------


def parse_metric(text: str, name: str) -> dict[tuple, float]:
    """Prometheus text → {sorted (label, value) tuple: sample} for ``name``."""
    out: dict[tuple, float] = {}
    for line in text.splitlines():
        m = re.match(rf"^{re.escape(name)}(?:\{{(.*)\}})?\s+(\S+)$", line)
        if m is None:
            continue
        labels = tuple(sorted(re.findall(r'(\w+)="([^"]*)"', m.group(1) or "")))
        out[labels] = float(m.group(2))
    return out


def dispatch_paths(metrics_text: str, op: str) -> dict[str, float]:
    """``fisco_device_dispatch_path_total`` for one op: {path: count}."""
    out: dict[str, float] = {}
    samples = parse_metric(metrics_text, "fisco_device_dispatch_path_total")
    for labels, value in samples.items():
        lab = dict(labels)
        if lab.get("op") == op:
            out[lab.get("path", "")] = value
    return out


def check_identity(ident: dict, want_platform: str) -> list[str]:
    got = (ident or {}).get("platform")
    if got != want_platform:
        return [f"ran on platform {got!r}, not {want_platform!r}"]
    return []


def check_health(health: dict) -> list[str]:
    """No degraded row — in particular none of device-crypto (breaker
    tripped), device-recompile (storm)."""
    return [
        f"/health row {name} is {row.get('status')}: {row.get('reason')}"
        for name, row in sorted((health or {}).get("components", {}).items())
        if row.get("status") != "ok"
    ]


def check_device_failures(device_doc: dict) -> list[str]:
    return [
        f"device program {op} failed {rec.get('count')}x and the host loop "
        f"answered instead: {rec.get('last_error')}"
        for op, rec in sorted((device_doc or {}).get("failures", {}).items())
    ]


def check_admission_dispatch(
    metrics_text: str, device_doc: dict, blocks: int, cutover: int
) -> list[str]:
    """Fused admission must have run on the device: at least one device
    dispatch per block, no breaker-open host fallback, and no native
    dispatch of a batch at or above the native cutover."""
    why = []
    paths = dispatch_paths(metrics_text, "admission")
    device = paths.get("device", 0.0)
    native = paths.get("native", 0.0)
    if device == 0 and native > 0:
        why.append(
            f"all {int(native)} admission dispatches went native — no EC "
            "program ran on the device"
        )
    elif device < blocks:
        why.append(
            f"{int(device)} device admission dispatches for {blocks} blocks"
        )
    if paths.get("host_fallback", 0.0) > 0:
        why.append(
            f"{int(paths['host_fallback'])} admission dispatches took the "
            "host fallback (device-crypto breaker open)"
        )
    biggest_native = (device_doc or {}).get("max_batch", {}).get(
        "admission_native", 0
    )
    if biggest_native >= cutover:
        why.append(
            f"a batch of {biggest_native} (>= cutover {cutover}) was "
            "dispatched to the native host loop"
        )
    return why


def check_air(doc: dict, want_platform: str) -> list[str]:
    why = check_identity(doc.get("device_doc", {}).get("device"), want_platform)
    client = doc.get("client", {})
    sent, acked = client.get("sent", 0), client.get("acknowledged", 0)
    if sent == 0 or acked != sent:
        why.append(f"{acked}/{sent} txs acknowledged by sendTransaction")
    if client.get("read_back") != acked:
        why.append(
            f"{client.get('read_back')}/{acked} acknowledged txs read back "
            "with getTransactionReceipt"
        )
    if client.get("bad_status"):
        why.append(f"{client['bad_status']} receipts with a non-zero status")
    if client.get("block_number", 0) < 1:
        why.append(f"getBlockNumber says {client.get('block_number')}")
    dev = doc.get("device_doc", {})
    ran = dev.get("compile_counts", {})
    compiled = {row.get("op") for row in dev.get("ledger", [])}
    for op in AIR_DEVICE_OPS:
        if op not in ran:
            why.append(f"/device shows no {op} program dispatched")
    if "keccak256" not in compiled:
        why.append("/device compile ledger holds no keccak256 program")
    why += check_device_failures(dev)
    why += check_health(doc.get("health"))
    if not doc.get("clean_sigterm"):
        why.append(f"no clean SIGTERM: {doc.get('sigterm_detail')}")
    return why


def check_lanes(name: str, cmp: dict) -> list[str]:
    """A lane-for-lane comparison with the native engine."""
    if not cmp:
        return [f"{name}: never compared with the native engine"]
    why = []
    if cmp.get("mismatch_lanes"):
        why.append(
            f"{name}: {len(cmp['mismatch_lanes'])} lanes differ from the "
            f"native engine (first {cmp['mismatch_lanes'][:5]})"
        )
    if "expected_invalid" in cmp:
        if cmp.get("invalid_lanes") != cmp["expected_invalid"]:
            why.append(
                f"{name}: validity bits lowered at {cmp.get('invalid_lanes')}"
                f", expected exactly {cmp['expected_invalid']}"
            )
    return why


def check_air4(doc: dict, want_platform: str, sizes: dict) -> list[str]:
    why = check_identity(doc.get("device"), want_platform)
    want = sizes["blocks"] * sizes["block_txs"]
    if doc.get("submitted") != want or doc.get("committed") != want:
        why.append(
            f"committed {doc.get('committed')} of {doc.get('submitted')} "
            f"submitted (want {want})"
        )
    if doc.get("rejected"):
        why.append(f"{doc['rejected']} txs rejected at admission")
    if len(set(doc.get("heights", []))) != 1 or len(doc.get("heights", [])) != 4:
        why.append(f"replicas at heights {doc.get('heights')}")
    if len(set(doc.get("state_roots", [None]))) != 1:
        why.append(f"replicas disagree on the state root: {doc.get('state_roots')}")
    why += check_lanes("first batch", doc.get("first_batch"))
    why += check_lanes("corrupted batch", doc.get("corrupted"))
    why += check_lanes("full-width block", doc.get("full_width"))
    dev = doc.get("device_doc", {})
    why += check_admission_dispatch(
        doc.get("metrics_text", ""), dev, doc.get("blocks", sizes["blocks"]),
        doc.get("cutover", 256),
    )
    why += check_device_failures(dev)
    why += check_health(doc.get("health"))
    if doc.get("breaker_state") not in (None, "closed"):
        why.append(f"device-crypto breaker is {doc['breaker_state']}")
    if doc.get("compiles_after_first_block", 1) != 0:
        why.append(
            f"{doc.get('compiles_after_first_block')} compile episodes inside "
            f"the driven window after the first block: "
            f"{doc.get('compiled_in_window')}"
        )
    count = (doc.get("device") or {}).get("count", 1)
    want_op = "admission" if count == 1 else "admission_sharded"
    got_op = (doc.get("full_width") or {}).get("op")
    if got_op != want_op:
        why.append(
            f"full-width block ran as {got_op!r}; with {count} device(s) "
            f"visible it takes {want_op!r}"
        )
    return why


def check_cache(doc: dict) -> list[str]:
    """The fresh child must have compiled nothing: cache hits only."""
    totals = doc.get("device_doc", {}).get("totals", {})
    why = []
    if totals.get("cold_compiles", 1) != 0:
        cold = [
            f"{r['op']}{r['shape']}"
            for r in doc.get("device_doc", {}).get("ledger", [])
            if r.get("cold_compiles")
        ]
        why.append(
            f"{totals.get('cold_compiles')} cold compiles in the cache child "
            f"(want 0): {cold[:8]}"
        )
    if totals.get("cache_hits", 0) <= 0:
        why.append("the cache child loaded nothing from the persistent cache")
    return why


# ---------------------------------------------------------------------------
# Children — each owns the backend its JAX_PLATFORMS names, alone
# ---------------------------------------------------------------------------


def child_identity(_args) -> int:
    import jax

    d = jax.devices()
    print(json.dumps({
        "platform": d[0].platform, "device_kind": d[0].device_kind,
        "count": len(d),
        "jax": jax.__version__,
        "libtpu": _dist_version("libtpu"),
    }))
    return 0


def _dist_version(name: str) -> str:
    from importlib import metadata

    try:
        return metadata.version(name)
    except metadata.PackageNotFoundError:
        return "not installed"


def child_air_client(args) -> int:
    """CPU-pinned load generator: the SDK client a user would write."""
    from concurrent.futures import ThreadPoolExecutor

    from fisco_bcos_tpu.codec.abi import ABICodec
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from fisco_bcos_tpu.sdk.client import Account, Client, RpcError

    client = Client(args.rpc, timeout=120.0)
    accounts = [Account() for _ in range(8)]
    codec = ABICodec(accounts[0].suite.hash)
    limit = client.get_block_number() + 500
    txs = [
        accounts[i % len(accounts)].sign_tx(
            to=DAG_TRANSFER_ADDRESS,
            data=codec.encode_call("userAdd(string,uint256)", f"air{i}", 1),
            block_limit=limit,
            nonce=f"chip-smoke-air-{i}",
        )
        for i in range(args.n)
    ]

    def send(tx):
        try:
            return client.send_raw_transaction(tx)["transactionHash"]
        except (RpcError, OSError) as e:
            return e

    with ThreadPoolExecutor(max_workers=16) as pool:
        results = list(pool.map(send, txs))
    acked = [r for r in results if isinstance(r, str)]
    errors = [repr(r) for r in results if not isinstance(r, str)]
    read_back = bad_status = 0
    deadline = time.monotonic() + args.wait
    pending = list(acked)
    while pending and time.monotonic() < deadline:
        still = []
        for h in pending:
            try:
                rc = client.get_transaction_receipt(h, False)
            except RpcError:
                still.append(h)
                continue
            read_back += 1
            bad_status += rc.get("status") != 0
        pending = still
        if pending:
            time.sleep(0.25)
    doc = {
        "sent": len(txs), "acknowledged": len(acked), "read_back": read_back,
        "bad_status": bad_status, "send_errors": errors[:5],
        "block_number": client.get_block_number(),
    }
    _write_json(args.out, doc)
    return 0


def child_air4(args) -> int:
    """Four in-process nodes sharing the chip through the DevicePlane (the
    one-chip mapping of BASELINE config #4), then one full-width block."""
    import numpy as np

    from fisco_bcos_tpu.utils.jaxenv import (
        configure_compile_cache,
        device_identity,
    )

    t_start = time.monotonic()
    cache_dir = configure_compile_cache()
    ident = device_identity()

    def say(msg: str) -> None:
        print(
            f"[{time.monotonic() - t_start:7.1f}s {ident['platform']}/"
            f"{ident['device_kind']} x{ident['count']}] {msg}", flush=True,
        )

    from fisco_bcos_tpu.codec.abi import ABICodec
    from fisco_bcos_tpu.crypto import admission
    from fisco_bcos_tpu.crypto.suite import ecdsa_suite
    from fisco_bcos_tpu.crypto.testvec import signed_payload_vectors
    from fisco_bcos_tpu.device.dispatch import device_breaker, device_min_batch
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from fisco_bcos_tpu.front import InprocGateway
    from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig
    from fisco_bcos_tpu.node import Node, NodeConfig
    from fisco_bcos_tpu.observability.device import (
        LEDGER,
        device_doc,
        install_observatory,
    )
    from fisco_bcos_tpu.ops.hash_common import bucket_batch
    from fisco_bcos_tpu.protocol.transaction import TransactionFactory
    from fisco_bcos_tpu.resilience import HEALTH
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    install_observatory()  # compile hooks live before the first compile
    suite = ecdsa_suite()
    codec = ABICodec(suite.hash)
    sign = suite.signature_impl
    block_txs, blocks = args.block_txs, args.blocks
    doc: dict = {
        "device": ident, "cache_dir": cache_dir, "blocks": blocks,
        "cutover": device_min_batch(),
    }

    # -- the chain: exactly ScenarioRunner._build_chain's shape
    keypairs = [sign.generate_keypair(secret=0xC41B + i) for i in range(4)]
    committee = [ConsensusNode(kp.pub, weight=1) for kp in keypairs]
    gw = InprocGateway(auto=True)
    nodes = []
    for kp in keypairs:
        cfg = NodeConfig(genesis=GenesisConfig(
            consensus_nodes=list(committee), tx_count_limit=block_txs,
        ))
        node = Node(cfg, keypair=kp)
        gw.connect(node.front)
        nodes.append(node)
    # Driven inline (no engine worker threads), as ScenarioRunner and the
    # one-core bench_flood do: with workers the replicas' state-root hash
    # batches coalesce in the plane by timing, so the merged bucket — and
    # with it the set of compiled hash programs — differs from run to run,
    # and "the fresh process compiles nothing" could not be a fixed check.

    fac = TransactionFactory(suite)
    senders = [sign.generate_keypair(secret=0x5E4D + i) for i in range(args.senders)]
    txs = [
        fac.create_signed(
            senders[i % len(senders)], chain_id="chain0", group_id="group0",
            block_limit=500, nonce=f"chip-smoke-{i}", to=DAG_TRANSFER_ADDRESS,
            input=codec.encode_call("userAdd(string,uint256)", f"u{i}", 1),
        )
        for i in range(blocks * block_txs)
    ]
    say(f"{len(txs)} txs signed by {len(senders)} senders; blocks of {block_txs}")

    def compare(payloads, sigs, expected_invalid=None, free=()) -> dict:
        """public admit_batch vs the native engine on the same bytes.
        ``expected_invalid``: the lanes whose validity bit must be lowered,
        and no other — except ``free`` lanes, which only have to agree."""
        nat = admission._admit_batch_native(payloads, sigs)
        dev = admission.admit_batch(payloads, sigs)
        ok_n, ok_d = np.asarray(nat[1]), np.asarray(dev[1])
        bad = ok_n != ok_d
        bad |= (np.asarray(nat[3]) != np.asarray(dev[3])).any(axis=1)  # tx hash
        both = ok_n & ok_d
        for k in (0, 2):  # sender, pubkey — defined on valid lanes
            bad |= both & (np.asarray(nat[k]) != np.asarray(dev[k])).any(axis=1)
        out = {
            "lanes": len(payloads),
            "mismatch_lanes": np.flatnonzero(bad).tolist(),
            "invalid_lanes": [
                i for i in np.flatnonzero(~ok_d).tolist() if i not in free
            ],
        }
        if expected_invalid is not None:
            out["expected_invalid"] = sorted(expected_invalid)
        return out

    # One full-width block's payloads and signatures. Its program (bucket 10,240) is compiled on
    # a second thread while the 1,024 one compiles on the plane worker — two
    # minutes-class compiles overlap instead of queueing. That is set-up only:
    # the checked call further down goes through the public admit_batch and
    # finds the program in the jit cache. The body is the one the plane
    # executor runs, so four visible chips warm the sharded program.
    width = args.full_width
    full_payloads, full_sigs, _digests, _pubs = signed_payload_vectors(
        width, unique=64,
        payload_fn=lambda i: b"bench parallel-transfer tx %06d" % i + b"\xab" * 64,
        secret_fn=lambda i: 0xBEEF + 104729 * i,
    )
    full_sigs = np.asarray(full_sigs, dtype=np.uint8)
    warm_error: list[str] = []

    def warm_full_width() -> None:
        try:
            admission._admit_batch_device(full_payloads, full_sigs, allow_shard=True)
        except Exception as e:  # the checked call below meets it again, counted
            warm_error.append(f"{type(e).__name__}: {e}")

    warm = threading.Thread(target=warm_full_width, name="warm-full-width")
    warm.start()

    first = txs[:block_txs]
    payloads = [t.encode_data() for t in first]
    sigs = np.stack([np.frombuffer(t.signature, np.uint8) for t in first])
    t0 = time.monotonic()
    doc["first_batch"] = compare(payloads, sigs, expected_invalid=[])
    say(
        f"first batch vs native: {len(doc['first_batch']['mismatch_lanes'])} "
        f"mismatching lanes of {block_txs} "
        f"({time.monotonic() - t0:.1f}s, first device call compiles)"
    )
    bad_sigs = sigs.copy()
    zero_s = [block_txs // 4, block_txs // 2, block_txs - 1]
    zero_r = [block_txs // 8]
    flipped = block_txs // 3 + 1
    bad_sigs[zero_s, 32:64] = 0  # s = 0 and r = 0 fail the range check
    bad_sigs[zero_r, :32] = 0
    # a flipped byte of r recovers some other key, or none when the candidate
    # x is off the curve: either way both engines must agree
    bad_sigs[flipped, 5] ^= 0xFF
    doc["corrupted"] = compare(
        payloads, bad_sigs, expected_invalid=zero_s + zero_r, free=[flipped]
    )
    say(
        f"corrupted batch: validity lowered at {doc['corrupted']['invalid_lanes']}"
        f", {len(doc['corrupted']['mismatch_lanes'])} lanes differ from native"
    )
    warm.join()
    doc["full_width_warm_error"] = warm_error[0] if warm_error else None
    say(f"full-width program ready (warm-up error: {doc['full_width_warm_error']})")

    # -- the flood
    def head() -> int:
        return max(nd.engine.consensus_head()[0] for nd in nodes)

    def leader_for(height: int):
        idx = nodes[0].pbft_config.leader_index(height, 0)
        target = nodes[0].pbft_config.nodes[idx].node_id
        return next(nd for nd in nodes if nd.node_id == target)

    def episodes() -> dict:
        return {
            (r["op"], r["shape"]): r["cold_compiles"] + r["cache_hits"]
            for r in LEDGER.snapshot()
        }

    def commit_pool(entry) -> bool:
        """Seal at whichever node leads until ``entry``'s pool is empty and
        every replica holds the tip; False when the chain stalls."""
        last_head, last_progress = head(), time.monotonic()
        while entry.txpool.pending_count() > 0:
            now, h = time.monotonic(), head()
            if h != last_head:
                last_head, last_progress = h, now
            elif now - last_progress > args.stall:
                say(f"STALLED at height {h} with "
                    f"{entry.txpool.pending_count()} txs pending")
                return False
            if not leader_for(h + 1).sealer.seal_and_submit():
                time.sleep(0.002)  # votes/2PCs drain on the workers
        for nd in nodes:
            nd.scheduler.drain_commits(60.0)
        tip = max(nd.block_number() for nd in nodes)
        t_conv = time.monotonic() + 30.0
        while (any(nd.block_number() < tip for nd in nodes)
               and time.monotonic() < t_conv):
            time.sleep(0.002)
        return True

    rejected = 0
    after_first: dict | None = None
    for b in range(blocks):
        batch = txs[b * block_txs:(b + 1) * block_txs]
        t0 = time.monotonic()
        entry = leader_for(head() + 1)
        results = entry.txpool.submit_batch(batch)
        rejected += sum(1 for r in results if r.status != 0)
        entry.tx_sync.maintain()  # gossip: the replicas admit on the sync lane
        if not commit_pool(entry):
            break
        say(f"block {b + 1}/{blocks}: {len(batch)} txs submitted at the leader, "
            f"chain at height {nodes[0].block_number()} after "
            f"{time.monotonic() - t0:.2f}s")
        if after_first is None:
            after_first = episodes()
    if after_first is None:
        in_window = {"(no block committed)": 1}
    else:
        grew = {k: n - after_first.get(k, 0) for k, n in episodes().items()}
        in_window = {f"{op}{shape}": n for (op, shape), n in grew.items() if n > 0}
    doc.update(
        submitted=len(txs),
        committed=nodes[0].ledger.total_transaction_count(),
        rejected=rejected,
        heights=[nd.block_number() for nd in nodes],
        state_roots=[
            nd.ledger.header_by_number(nd.block_number()).state_root.hex()
            for nd in nodes
        ],
        compiles_after_first_block=sum(in_window.values()),
        compiled_in_window=in_window,
    )

    # -- the full-width block through the public admit_batch
    items = "fisco_device_items_total"
    before = parse_metric(REGISTRY.render(), items)
    t0 = time.monotonic()
    full = compare(full_payloads, full_sigs, expected_invalid=[])
    grown = [
        dict(labels).get("op")
        for labels, v in parse_metric(REGISTRY.render(), items).items()
        if v - before.get(labels, 0.0) >= width
        and dict(labels).get("op", "").startswith("admission")
        and dict(labels).get("op") != "admission_native"
    ]
    full.update(bucket=bucket_batch(width), op=grown[0] if grown else None)
    doc["full_width"] = full
    say(
        f"full-width block: {width} lanes at bucket {full['bucket']} ran as "
        f"op={full['op']}, {len(full['mismatch_lanes'])} lanes differ from "
        f"native ({time.monotonic() - t0:.1f}s incl. the native reference)"
    )

    for nd in nodes:
        nd.stop()
    import jax

    doc["placement"] = {
        # where the work landed: peak bytes each device ever held
        str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()
    }
    say(f"peak bytes in use per device: {doc['placement']}")
    doc.update(
        metrics_text="\n".join(
            ln for ln in REGISTRY.render().splitlines()
            if ln.startswith((
                "fisco_device_dispatch_path_total",
                "fisco_device_program_failures_total",
                "fisco_device_plane_dispatch_total",
            ))
        ),
        device_doc=device_doc(),
        health=HEALTH.snapshot(),
        breaker_state=device_breaker().state,
        wall_s=round(time.monotonic() - t_start, 1),
    )
    _write_json(args.out, doc)
    say(f"document -> {args.out}")
    return 0


CHILDREN = {
    "identity": child_identity,
    "air-client": child_air_client,
    "air4": child_air4,
}


# ---------------------------------------------------------------------------
# The orchestrating parent — no JAX, no package import
# ---------------------------------------------------------------------------


def _write_json(path: str, doc: dict) -> None:
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)


class Smoke:
    def __init__(self, rehearse: bool):
        self.rehearse = rehearse
        self.platform = "cpu" if rehearse else "tpu"
        self.sizes = REHEARSAL if rehearse else REAL
        self.ident: dict = {}
        self.t0 = time.monotonic()
        self.procs: list[subprocess.Popen] = []
        self.node_pid: int | None = None
        self.failed: list[str] = []
        self.setup: dict[str, float] = {}

    # -- output: every line names the device (and says when it is a rehearsal)

    def say(self, msg: str) -> None:
        dev = (
            f"{self.ident.get('platform', self.platform)}/"
            f"{self.ident.get('device_kind', '?')} x{self.ident.get('count', '?')}"
        )
        tag = "REHEARSAL on the CPU, not a pass | " if self.rehearse else ""
        print(
            f"chip_smoke [{time.monotonic() - self.t0:6.1f}s {dev}] {tag}{msg}",
            flush=True,
        )

    def verdict(self, phase: str, why: list[str]) -> None:
        if why:
            self.failed.append(phase)
            for w in why:
                self.say(f"phase {phase}: FAIL: {w}")
        else:
            self.say(f"phase {phase}: ok")

    # -- processes

    def env(self, platform: str) -> dict:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = platform
        env["PYTHONPATH"] = HERE + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        env.setdefault("FISCO_FLIGHT_DIR", OUT)
        if self.rehearse and platform == "cpu":
            # the CPU rehearsal forces the device leg (a CPU backend routes
            # every batch to the native loop) at the test tier's tiny bucket
            env["FISCO_FORCE_DEVICE_ADMISSION"] = "1"
            env["FISCO_TEST_BUCKET"] = "32"
            if "xla_backend_optimization_level" not in env.get("XLA_FLAGS", ""):
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "")
                    + " --xla_backend_optimization_level=0"
                    " --xla_llvm_disable_expensive_passes=true"
                ).strip()
        return env

    def remaining(self) -> float:
        limit = 3 * TIME_LIMIT_S if self.rehearse else TIME_LIMIT_S
        return limit - 30.0 - (time.monotonic() - self.t0)

    def run_child(self, name: str, argv: list[str], platform: str,
                  cap_s: float) -> tuple[int, str]:
        """Run one child to its end (or its deadline); (rc, log path)."""
        log = os.path.join(OUT, f"{name}.log")
        timeout = max(5.0, min(cap_s, self.remaining()))
        with open(log, "w") as f:
            proc = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), *argv],
                cwd=HERE, env=self.env(platform), stdout=f,
                stderr=subprocess.STDOUT, start_new_session=True,
            )
            self.procs.append(proc)
            try:
                rc = proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.kill(proc)
                rc = -1
                f.write(f"\nchip_smoke: killed at its {timeout:.0f}s deadline\n")
        return rc, log

    @staticmethod
    def kill(proc: subprocess.Popen) -> None:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()

    def cleanup(self) -> None:
        for proc in self.procs:
            self.kill(proc)
        if self.node_pid is not None and _alive(self.node_pid):
            os.kill(self.node_pid, signal.SIGKILL)

    @staticmethod
    def tail(path: str, n: int = 6) -> str:
        lines = [ln for ln in _lines(path) if ln.strip()]
        return " | ".join(lines[-n:])[-1200:] or "(no log)"

    # -- phases

    def preflight(self) -> bool:
        """Which device do children land on? One line, or the reason."""
        rc, log = self.run_child("identity", ["--child", "identity"],
                                 self.platform, 180.0)
        if rc != 0:
            print(
                f"chip_smoke: no {self.platform} backend — refusing to run: "
                f"{self.tail(log, 1)}", flush=True,
            )
            return False
        self.ident = json.loads(
            [ln for ln in _lines(log) if ln.startswith("{")][-1]
        )
        if self.ident["platform"] != self.platform:
            print(
                f"chip_smoke: children land on platform "
                f"{self.ident['platform']!r}, not {self.platform!r} — "
                "refusing to run", flush=True,
            )
            return False
        self.say(
            f"jax {self.ident['jax']}, libtpu {self.ident['libtpu']}; reduced: "
            + REDUCED.format(
                txs=self.sizes["blocks"] * self.sizes["block_txs"],
                b1=self.sizes["block_txs"], b2=self.sizes["full_width"],
            )
        )
        return True

    def phase_air(self) -> None:
        chain = os.path.join(OUT, "air_chain")
        subprocess.run(["rm", "-rf", chain], check=True)
        p2p, rpc = _free_ports(2)
        built = subprocess.run(
            [sys.executable, "-m", "fisco_bcos_tpu.tool.build_chain",
             "-l", "127.0.0.1:1", "-o", chain, "-p", f"{p2p},{rpc}"],
            cwd=HERE, env=self.env("cpu"), capture_output=True, text=True,
            timeout=300,
        )
        if built.returncode != 0:
            return self.verdict("air", [
                "build_chain failed: " + (built.stderr or built.stdout)[-400:]
            ])
        node_dir = os.path.join(chain, "node0")
        node_log = os.path.join(node_dir, "node.log")
        doc: dict = {"rpc_port": rpc}
        try:
            subprocess.run(
                ["bash", os.path.join(node_dir, "start.sh")],
                env=self.env(self.platform), check=True, capture_output=True,
                timeout=60,
            )
            with open(os.path.join(node_dir, "node.pid")) as f:
                self.node_pid = int(f.read().strip())
            url = f"http://127.0.0.1:{rpc}"
            t0 = time.monotonic()
            if not self.wait_rpc(url, min(300.0, self.remaining())):
                return self.verdict("air", [
                    "node never answered JSON-RPC: " + self.tail(node_log)
                ])
            self.say(f"phase air: node0 answers RPC {time.monotonic() - t0:.1f}s "
                     "after start.sh")
            client_out = os.path.join(OUT, "air_client.json")
            rc, log = self.run_child(
                "air_client",
                ["--child", "air-client", "--rpc", url,
                 "--n", str(self.sizes["air_txs"]), "--out", client_out,
                 "--wait", str(int(min(600.0, self.remaining() - 60)))],
                "cpu", 900.0,
            )
            if rc != 0:
                return self.verdict("air", [
                    f"RPC client exited {rc}: {self.tail(log)}"
                ])
            with open(client_out) as f:
                doc["client"] = json.load(f)
            doc["device_doc"] = json.loads(_get(url + "/device"))
            doc["health"] = json.loads(_get(url + "/health"))
            metrics = _get(url + "/metrics")
            with open(os.path.join(OUT, "air_metrics.txt"), "w") as f:
                f.write(metrics)
        finally:
            doc["clean_sigterm"], doc["sigterm_detail"] = self.stop_node(node_log)
            _write_json(os.path.join(OUT, "air.json"), doc)
        if doc.get("device_doc"):
            # the node's own word on where it ran
            totals = doc["device_doc"].get("totals", {})
            self.setup["air (node0)"] = totals.get("compile_ms", 0.0) / 1e3
            c = doc["client"]
            self.say(
                f"phase air: {c['acknowledged']}/{c['sent']} acknowledged, "
                f"{c['read_back']} read back, height {c['block_number']}; "
                f"node on {doc['device_doc'].get('device')}; "
                f"{totals.get('cold_compiles')} cold compiles + "
                f"{totals.get('cache_hits')} cache loads, "
                f"{totals.get('compile_ms', 0) / 1e3:.1f}s compiling"
            )
        self.verdict("air", check_air(doc, self.platform))

    def wait_rpc(self, url: str, timeout: float) -> bool:
        body = json.dumps({
            "jsonrpc": "2.0", "id": 1, "method": "getBlockNumber", "params": [],
        }).encode()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if not _alive(self.node_pid):
                return False
            try:
                req = urllib.request.Request(
                    url, data=body, headers={"Content-Type": "application/json"}
                )
                with urllib.request.urlopen(req, timeout=5) as resp:
                    if "result" in json.loads(resp.read()):
                        return True
            except (OSError, ValueError):
                time.sleep(0.5)
        return False

    def stop_node(self, node_log: str) -> tuple[bool, str]:
        """SIGTERM node0 and judge the exit: gone within the drain window,
        having logged the shutdown, with no traceback after it."""
        pid = self.node_pid
        if pid is None or not _alive(pid):
            return False, "node was not running: " + self.tail(node_log, 3)
        os.kill(pid, signal.SIGTERM)
        deadline = time.monotonic() + 90.0
        while _alive(pid) and time.monotonic() < deadline:
            time.sleep(0.2)
        if _alive(pid):
            os.kill(pid, signal.SIGKILL)
            return False, "still alive 90s after SIGTERM (killed)"
        with open(node_log, errors="replace") as f:
            text = f.read()
        at = text.rfind("shutting down")
        if at < 0:
            return False, "exited without logging the shutdown"
        if "Traceback" in text[at:]:
            return False, "traceback during shutdown: " + self.tail(node_log, 3)
        return True, "exited after SIGTERM"

    def phase_air4(self, name: str, blocks: int) -> dict | None:
        out = os.path.join(OUT, f"{name}.json")
        if os.path.exists(out):
            os.remove(out)
        s = self.sizes
        rc, log = self.run_child(
            name,
            ["--child", "air4", "--out", out, "--blocks", str(blocks),
             "--block-txs", str(s["block_txs"]), "--senders", str(s["senders"]),
             "--full-width", str(s["full_width"]),
             "--stall", "1500" if self.rehearse else "600"],
            self.platform, 3000.0,
        )
        for ln in _lines(log):
            if ln.startswith("["):
                self.say(f"phase {name}: {ln}")
        if rc != 0 or not os.path.exists(out):
            self.verdict(name, [f"child exited {rc}: {self.tail(log)}"])
            return None
        with open(out) as f:
            doc = json.load(f)
        rows = doc["device_doc"]["ledger"]
        for r in rows:
            if r["compile_ms"] >= 1000.0:
                self.say(
                    f"phase {name}: set-up: {r['op']}{r['shape']} "
                    f"{r['compile_ms'] / 1e3:.1f}s "
                    f"({'cold compile' if r['cold_compiles'] else 'cache load'})"
                )
        totals = doc["device_doc"]["totals"]
        self.setup[name] = totals["compile_ms"] / 1e3
        self.say(
            f"phase {name}: {totals['cold_compiles']} cold compiles + "
            f"{totals['cache_hits']} cache loads, {totals['compile_ms'] / 1e3:.1f}s "
            f"compiling of {doc['wall_s']}s; dispatch paths "
            f"{dispatch_paths(doc['metrics_text'], 'admission')}"
        )
        sizes = dict(s, blocks=blocks)
        self.verdict(name, check_air4(doc, self.platform, sizes))
        return doc

    def run(self, phases: list[str]) -> int:
        os.makedirs(OUT, exist_ok=True)
        try:
            if not self.preflight():
                return 2
            if "air" in phases:
                self.phase_air()
            if "air4" in phases:
                self.phase_air4("air4", self.sizes["blocks"])
            if "cache" in phases:
                doc = self.phase_air4("cache", 1)
                if doc is not None:
                    self.verdict("cache (fresh process, warm cache)",
                                 check_cache(doc))
        finally:
            self.cleanup()
        wall = time.monotonic() - self.t0
        self.say(
            "set-up (compile seconds per phase): "
            + ", ".join(f"{k} {v:.1f}s" for k, v in self.setup.items())
            + f"; wall {wall:.0f}s"
        )
        if self.failed:
            self.say(f"FAILED phases: {', '.join(self.failed)}")
            return 1
        if self.rehearse:
            self.say("rehearsal complete: every check that can hold on a CPU "
                     "held. This is not a pass; run it on the chip.")
            return RC_REHEARSAL_DONE
        if set(phases) != {"air", "air4", "cache"}:
            self.say(f"only {phases} ran: no result without every phase")
            return 1
        if wall > TIME_LIMIT_S:
            self.say(f"took {wall:.0f}s, over the {TIME_LIMIT_S:.0f}s limit")
            return 1
        print(json.dumps({"ok": True, "device": {
            "platform": self.ident["platform"],
            "kind": self.ident["device_kind"],
            "count": self.ident["count"],
        }}), flush=True)
        return 0


def _alive(pid: int | None) -> bool:
    if pid is None:
        return False
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def _free_ports(n: int) -> list[int]:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _get(url: str) -> str:
    with urllib.request.urlopen(url, timeout=30) as resp:
        return resp.read().decode()


def _lines(path: str) -> list[str]:
    try:
        with open(path, errors="replace") as f:
            return [ln.rstrip() for ln in f]
    except OSError:
        return []


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; never a pass (exit 3 when done)")
    ap.add_argument("--phase", action="append", choices=["air", "air4", "cache"],
                    help="run only these phases (debugging; no result line)")
    ap.add_argument("--child", choices=sorted(CHILDREN), help=argparse.SUPPRESS)
    for flag, typ in (("--rpc", str), ("--out", str), ("--n", int),
                      ("--wait", float), ("--blocks", int), ("--block-txs", int),
                      ("--senders", int), ("--full-width", int),
                      ("--stall", float)):
        ap.add_argument(flag, type=typ, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "fisco_bcos_tpu")):
        print(
            "chip_smoke: fisco_bcos_tpu/ is not next to this script — it "
            "drives the repository and cannot run without it", flush=True,
        )
        return 2
    if args.child:
        sys.path.insert(0, HERE)
        return CHILDREN[args.child](args)
    return Smoke(args.rehearse).run(args.phase or ["air", "air4", "cache"])


if __name__ == "__main__":
    sys.exit(main())

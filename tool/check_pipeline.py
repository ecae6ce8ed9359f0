#!/usr/bin/env python
"""Pipeline-observatory smoke check (ISSUE 9 CI acceptance).

Floods a 4-node in-process PBFT chain, then asserts:

- ``GET /pipeline`` serves the stage-occupancy document with a saturated
  stage (busy time recorded) and at least one blocked-on attribution edge
  (``<stage> blocked_on=<what>``), plus non-empty backpressure watermark
  timelines;
- the sampling profiler's top self-time frame lands inside the package
  while package code is the only thing running.

Runnable locally and from CI::

    python tool/check_pipeline.py [--txs N] [--block-cap N]

Exit 0 on success, 1 with a named failure otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from fisco_bcos_tpu.utils.jaxenv import pin_cpu  # noqa: E402

# a CPU smoke: CPU platform, fast-compile XLA flags, the test suite's 32-lane
# batch bucket and the shared compile cache
pin_cpu()


def fail(msg: str) -> None:
    print(f"FAIL: {msg}")
    raise SystemExit(1)


def _build_chain(block_cap: int, secret_base: int, n_nodes: int = 4):
    """One 4-node in-proc chain + tx maker + leader lookup — shared by the
    inline observatory flood and the worker-driven pipelined flood so the
    bootstrap recipe cannot drift between the two legs."""
    from fisco_bcos_tpu.codec.abi import ABICodec
    from fisco_bcos_tpu.crypto.suite import ecdsa_suite
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from fisco_bcos_tpu.front import InprocGateway
    from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig
    from fisco_bcos_tpu.node import Node, NodeConfig
    from fisco_bcos_tpu.protocol.transaction import TransactionFactory

    suite = ecdsa_suite()
    codec = ABICodec(suite.hash)
    keypairs = [
        suite.signature_impl.generate_keypair(secret=secret_base + i)
        for i in range(n_nodes)
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

    fac = TransactionFactory(suite)
    sender = suite.signature_impl.generate_keypair(secret=secret_base + 99)

    def make_txs(prefix: str, n: int):
        return [
            fac.create_signed(
                sender, chain_id="chain0", group_id="group0", block_limit=500,
                nonce=f"{prefix}-{i}", to=DAG_TRANSFER_ADDRESS,
                input=codec.encode_call(
                    "userAdd(string,uint256)", f"{prefix}{i}", 1
                ),
            )
            for i in range(n)
        ]

    def leader_for(height: int):
        idx = nodes[0].pbft_config.leader_index(height, 0)
        target = nodes[0].pbft_config.nodes[idx].node_id
        return next(nd for nd in nodes if nd.node_id == target)

    return nodes, make_txs, leader_for


def run_chain(n_txs: int, block_cap: int) -> None:
    nodes, make_txs, leader_for = _build_chain(block_cap, secret_base=0x919E)
    txs = make_txs("pipe", n_txs)
    entry = nodes[0]
    results = entry.txpool.submit_batch(txs)
    rejected = sum(1 for r in results if r.status != 0)
    if rejected:
        fail(f"{rejected}/{n_txs} txs rejected at admission")
    entry.tx_sync.maintain()
    stalls = 0
    while entry.txpool.pending_count() > 0 and stalls < 5:
        if not leader_for(nodes[0].block_number() + 1).sealer.seal_and_submit():
            stalls += 1
    if entry.txpool.pending_count() > 0:
        fail(f"chain stalled with {entry.txpool.pending_count()} txs pending")
    # ISSUE 15: the flood leg ends with the chain-safety auditor —
    # agreement / integrity / certificates across all four replicas
    from fisco_bcos_tpu.consensus.audit import audit_chain

    audit = audit_chain(nodes)
    if not audit["ok"]:
        fail(f"flood chain-safety audit: {audit['violations']}")
    print(
        f"chain ok: {nodes[0].block_number()} blocks, {n_txs} txs "
        f"committed on 4 nodes, audit clean "
        f"({audit['headers_checked']} headers)"
    )


def run_pipelined_flood(n_txs: int = 64, block_cap: int = 16) -> None:
    """ISSUE 14 smoke: a worker-driven (overlapped) flood over a fresh
    4-node chain must drain with the sealer NO LONGER sticky-blocked on
    ``consensus_quorum`` — pre-campaign, the sealer parked there (or on
    ``2pc_commit``) for essentially the whole flood whenever a proposal
    was in flight; with the optimistic head + async commit it keeps
    sealing ahead."""
    import time

    from fisco_bcos_tpu.observability.pipeline import PIPELINE, pipeline_doc

    nodes, make_txs, leader_for = _build_chain(block_cap, secret_base=0x14E)
    for node in nodes:
        node.engine.start_worker()
    PIPELINE.reset()
    t0 = time.monotonic()
    try:
        txs = make_txs("pf", n_txs)
        entry = nodes[0]
        results = entry.txpool.submit_batch(txs)
        if any(r.status != 0 for r in results):
            fail("pipelined flood: txs rejected at admission")
        entry.tx_sync.maintain()
        deadline = time.monotonic() + 120
        while entry.txpool.pending_count() > 0:
            if time.monotonic() > deadline:
                fail("pipelined flood did not drain in 120s")
            head = max(nd.engine.consensus_head()[0] for nd in nodes)
            if not leader_for(head + 1).sealer.seal_and_submit():
                time.sleep(0.002)
        for nd in nodes:
            if not nd.scheduler.drain_commits(60.0):
                fail("commit worker failed to drain")
        t_conv = time.monotonic() + 30
        while len({nd.block_number() for nd in nodes}) != 1:
            if time.monotonic() > t_conv:
                fail(
                    "replicas diverged: "
                    f"{sorted({nd.block_number() for nd in nodes})}"
                )
            time.sleep(0.01)
        # one idle tick so the sealer's final sticky state is honest
        leader_for(nodes[0].block_number() + 1).sealer.generate_proposal()
    finally:
        for node in nodes:
            node.engine.stop_worker()
    window_ms = (time.monotonic() - t0) * 1e3
    sealer = pipeline_doc()["stages"].get("sealer")
    if sealer is None:
        fail("no sealer stage recorded during the pipelined flood")
    if sealer["state"] == "blocked":
        fail("sealer left sticky-blocked after the flood drained")
    quorum_ms = sealer["blocked_ms"].get("consensus_quorum", 0.0)
    twopc_ms = sealer["blocked_ms"].get("2pc_commit", 0.0)
    # the async commit's signature: the sealer NEVER parks behind a 2PC
    # (pre-campaign this was the dominant edge — the optimistic head
    # advances at checkpoint booking, before the 2PC runs)
    if twopc_ms > 0.2 * window_ms:
        fail(
            f"sealer parked behind the 2PC for {twopc_ms:.0f}ms of a "
            f"{window_ms:.0f}ms flood — async commit not engaged"
        )
    # vote rounds still block the sealer between prebuilds (honest wall
    # on a contended host) — only a whole-flood park is the pre-campaign
    # sticky behavior
    if quorum_ms > 0.9 * window_ms:
        fail(
            f"sealer sticky-blocked on consensus_quorum for "
            f"{quorum_ms:.0f}ms of a {window_ms:.0f}ms flood"
        )
    # ISSUE 15: the pipelined leg's overlap (optimistic head, async 2PC,
    # prebuilds) must still land a chain every replica agrees on
    from fisco_bcos_tpu.consensus.audit import audit_chain

    audit = audit_chain(nodes)
    if not audit["ok"]:
        fail(f"pipelined flood chain-safety audit: {audit['violations']}")
    print(
        f"pipelined flood ok: {nodes[0].block_number()} blocks, "
        f"{n_txs} txs on 4 worker-driven nodes in {window_ms:.0f} ms; "
        f"sealer blocked: consensus_quorum={quorum_ms:.0f}ms "
        f"2pc_commit={twopc_ms:.0f}ms, final state={sealer['state']}; "
        f"audit clean ({audit['headers_checked']} headers)"
    )


def check_pipeline_endpoint() -> None:
    from fisco_bcos_tpu.observability import profiler
    from fisco_bcos_tpu.observability.pipeline import PIPELINE, pipeline_doc
    from fisco_bcos_tpu.rpc.http_server import RpcHttpServer

    PIPELINE.sample_once()
    server = RpcHttpServer(
        impl=None, port=0, pipeline=pipeline_doc, profile=profiler.profile
    )
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(f"{base}/pipeline", timeout=10) as resp:
            if not resp.headers["Content-Type"].startswith("application/json"):
                fail("/pipeline content type is not application/json")
            doc = json.loads(resp.read())
    finally:
        server.stop()
    stages = doc.get("stages") or {}
    if not stages:
        fail("/pipeline served no stages after a flood")
    expected = {"admission", "sealer", "consensus", "execute", "commit"}
    missing = expected - set(stages)
    if missing:
        fail(f"/pipeline missing stages: {sorted(missing)}")
    busiest, busiest_ms = max(
        ((s, v["busy_ms"]) for s, v in stages.items()), key=lambda kv: kv[1]
    )
    if busiest_ms <= 0:
        fail("no stage recorded busy time during the flood")
    edges = [
        (s, on, ms)
        for s, v in stages.items()
        for on, ms in v["blocked_ms"].items()
    ]
    if not edges:
        fail("no blocked-on attribution edge recorded during the flood")
    if not doc.get("watermarks"):
        fail("no backpressure watermark timelines recorded")
    top = max(edges, key=lambda e: e[2])
    print(
        f"pipeline ok: {len(stages)} stages, busiest={busiest} "
        f"({busiest_ms:.0f} ms busy), top edge {top[0]} "
        f"blocked_on={top[1]} ({top[2]:.1f} ms), "
        f"{len(doc['watermarks'])} watermark series"
    )


def check_profiler() -> None:
    """The profiler's top self-time frame must land in the package while a
    package hot loop is the only work in the process."""
    from fisco_bcos_tpu.crypto.ref.keccak import keccak256
    from fisco_bcos_tpu.observability.profiler import SamplingProfiler

    stop = threading.Event()

    def spin():
        data = b"pipeline-observatory"
        while not stop.is_set():
            data = keccak256(data)

    t = threading.Thread(target=spin, daemon=True)
    t.start()
    try:
        p = SamplingProfiler(hz=200.0)
        p.run_for(1.0)
    finally:
        stop.set()
        t.join(timeout=5)
    report = p.report()
    if report["samples"] < 50:
        fail(f"profiler took only {report['samples']} samples in 1s")
    if not report["self_top"]:
        fail("profiler folded no package stacks while package code spun")
    top = report["self_top"][0]["func"]
    if "fisco_bcos_tpu" not in top:
        fail(f"profiler top frame outside the package: {top}")
    if not report["collapsed"]:
        fail("no collapsed stacks in the profiler report")
    print(
        f"profiler ok: {report['samples']} sweeps, top self frame {top} "
        f"({report['self_top'][0]['pct']}%), duty cycle "
        f"{report['overhead']['duty_cycle'] * 100:.2f}%"
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--txs", type=int, default=96)
    ap.add_argument("--block-cap", type=int, default=32)
    args = ap.parse_args()
    run_chain(args.txs, args.block_cap)
    check_pipeline_endpoint()
    check_profiler()
    run_pipelined_flood()
    print("PASS: pipeline observatory + overlapped pipeline live end to end")
    return 0


if __name__ == "__main__":
    sys.exit(main())

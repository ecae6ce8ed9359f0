#!/usr/bin/env python
"""Telemetry smoke check: run a 4-node in-process PBFT chain for a few
blocks, then assert the observability layer saw it.

Checks (ISSUE 1 acceptance):
- `fisco_block_execute_latency_ms` / `fisco_block_commit_latency_ms`
  histograms populated with the reference-matched 0/50/100/150 ms buckets
  (mtail contract, tools/BcosAirBuilder/build_chain.sh:920-935);
- the trace ring holds a committed block's span chain
  (admission -> seal -> PBFT phases -> execute -> commit);
- `GET /metrics` and `GET /trace` serve both over rpc/http_server.py.

Runnable locally and from CI::

    python tool/check_telemetry.py [--txs N] [--block-cap N]

Exit 0 on success, 1 with a named failure otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
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


def run_chain(n_txs: int, block_cap: int) -> None:
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
        suite.signature_impl.generate_keypair(secret=0x7E1E + i) for i in range(4)
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
    sender = suite.signature_impl.generate_keypair(secret=0x7E1E99)
    txs = [
        fac.create_signed(
            sender,
            chain_id="chain0",
            group_id="group0",
            block_limit=500,
            nonce=f"telemetry-{i}",
            to=DAG_TRANSFER_ADDRESS,
            input=codec.encode_call("userAdd(string,uint256)", f"t{i}", 1),
        )
        for i in range(n_txs)
    ]
    entry = nodes[0]
    results = entry.txpool.submit_batch(txs)
    rejected = sum(1 for r in results if r.status != 0)
    if rejected:
        fail(f"{rejected}/{n_txs} txs rejected at admission")
    entry.tx_sync.maintain()

    def leader_for_next(height: int):
        idx = nodes[0].pbft_config.leader_index(height, 0)
        target = nodes[0].pbft_config.nodes[idx].node_id
        return next(nd for nd in nodes if nd.node_id == target)

    stalls = 0
    while entry.txpool.pending_count() > 0 and stalls < 5:
        leader = leader_for_next(nodes[0].block_number() + 1)
        if not leader.sealer.seal_and_submit():
            stalls += 1
    if entry.txpool.pending_count() > 0:
        fail(f"chain stalled with {entry.txpool.pending_count()} txs pending")
    height = nodes[0].block_number()
    blocks_expected = -(-n_txs // block_cap)
    if height < blocks_expected:
        fail(f"only {height} blocks committed, expected >= {blocks_expected}")
    print(f"chain ok: {height} blocks, {n_txs} txs committed on 4 nodes")


def check_metrics_text(text: str) -> None:
    for family in ("fisco_block_execute_latency_ms", "fisco_block_commit_latency_ms"):
        if f"# TYPE {family} histogram" not in text:
            fail(f"{family} histogram family missing from /metrics")
        for edge in ("0", "50", "100", "150", "+Inf"):
            if f'{family}_bucket{{le="{edge}"}}' not in text:
                fail(f"{family} missing mtail bucket le={edge}")
        count_line = next(
            (
                ln
                for ln in text.splitlines()
                if ln.startswith(f"{family}_count")
            ),
            None,
        )
        if count_line is None or float(count_line.split()[-1]) <= 0:
            fail(f"{family}_count not populated: {count_line}")
    print("metrics ok: block exec/commit histograms populated, mtail buckets")


def check_trace(trace: dict) -> None:
    events = trace.get("traceEvents")
    if not events:
        fail("trace is empty")
    names = {e["name"] for e in events}
    required = {
        "txpool.submit_batch",  # admission
        "seal",
        "pbft.pre_prepare",
        "pbft.prepare",
        "pbft.commit",
        "pbft.checkpoint",
        "scheduler.execute_block",
        "scheduler.commit_block",
    }
    missing = required - names
    if missing:
        fail(f"trace missing spans: {sorted(missing)}")
    # nesting by REAL span ids (ISSUE 4 satellite: the parent NAME is just a
    # display label — the id is unambiguous even for concurrent same-name
    # stages): the ledger commit runs inside the checkpoint handler's span
    ckpt_ids = {
        e["args"]["span_id"]
        for e in events
        if e["name"] == "pbft.checkpoint_commit"
    }
    nested = [
        e
        for e in events
        if e["name"] == "scheduler.commit_block"
        and e.get("args", {}).get("parent_id") in ckpt_ids
    ]
    if not nested:
        fail("scheduler.commit_block not nested under pbft.checkpoint_commit")
    if nested[0]["args"].get("parent") != "pbft.checkpoint_commit":
        fail("display-label parent missing from nested span args")
    print(f"trace ok: {len(events)} spans, full block pipeline present")


def check_http() -> None:
    from fisco_bcos_tpu.observability import TRACER
    from fisco_bcos_tpu.observability.device import device_doc
    from fisco_bcos_tpu.rpc.http_server import RpcHttpServer
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    server = RpcHttpServer(
        impl=None, port=0, metrics=REGISTRY, tracer=TRACER, device=device_doc
    )
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as resp:
            check_metrics_text(resp.read().decode())
        with urllib.request.urlopen(f"{base}/trace", timeout=10) as resp:
            if not resp.headers["Content-Type"].startswith("application/json"):
                fail("/trace content type is not application/json")
            check_trace(json.loads(resp.read()))
        with urllib.request.urlopen(f"{base}/device", timeout=10) as resp:
            check_device(json.loads(resp.read()))
    finally:
        server.stop()
    print("http ok: GET /metrics, GET /trace and GET /device served")


def check_device(doc: dict) -> None:
    """ISSUE 13 smoke: the device observatory document is served and the
    chain run populated it — per-op phase totals with an execute segment,
    and a ledger whose rows carry cold-vs-cache attribution fields."""
    for key in ("ledger", "phase_ms", "storm", "totals", "compile_counts"):
        if key not in doc:
            fail(f"/device missing {key}")
    if not doc.get("enabled"):
        fail("/device reports the observatory disabled")
    if not doc["phase_ms"]:
        fail("/device phase_ms empty after a chain run")
    if not any("execute" in ph for ph in doc["phase_ms"].values()):
        fail("/device has no execute phase for any op")
    for row in doc["ledger"]:
        for field in ("op", "shape", "cold_compiles", "cache_hits",
                      "last_source"):
            if field not in row:
                fail(f"/device ledger row missing {field}: {row}")
    print(
        f"device ok: {len(doc['phase_ms'])} op(s) attributed, "
        f"{doc['totals']['cold_compiles']} cold compile(s), "
        f"{doc['totals']['cache_hits']} cache load(s)"
    )


def check_split_trace_tx() -> None:
    """ISSUE 4 acceptance smoke: a Pro-split deployment (node core +
    storage service here, the RPC front door as its OWN OS process) serves
    `GET /trace/tx/<hash>` with a stitched lifecycle covering >= 5 stages
    across >= 2 processes."""
    import subprocess

    from fisco_bcos_tpu.codec.abi import ABICodec
    from fisco_bcos_tpu.crypto.suite import ecdsa_suite
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig
    from fisco_bcos_tpu.node import Node, NodeConfig
    from fisco_bcos_tpu.observability import TRACER
    from fisco_bcos_tpu.protocol.transaction import TransactionFactory
    from fisco_bcos_tpu.rpc.jsonrpc import JsonRpcImpl
    from fisco_bcos_tpu.service import StorageService
    from fisco_bcos_tpu.service.rpc_service import RpcFacade
    from fisco_bcos_tpu.storage import MemoryStorage
    from fisco_bcos_tpu.utils.bytesutil import to_hex

    suite = ecdsa_suite()
    codec = ABICodec(suite.hash)
    storage_svc = StorageService(MemoryStorage())
    storage_svc.start()
    kp = suite.signature_impl.generate_keypair(secret=0x7E1EAA)
    node = Node(
        NodeConfig(
            genesis=GenesisConfig(consensus_nodes=[ConsensusNode(kp.pub)]),
            storage_endpoints=f"{storage_svc.host}:{storage_svc.port}",
        ),
        keypair=kp,
    )
    facade = RpcFacade(JsonRpcImpl(node), tracer=TRACER)
    facade.start()
    env = dict(os.environ, PYTHONPATH=_REPO)
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "fisco_bcos_tpu.service", "rpc",
            "--facade", f"{facade.host}:{facade.port}",
        ],
        stdout=subprocess.PIPE,
        text=True,
        cwd=_REPO,
        env=env,
    )
    try:
        ready = proc.stdout.readline().strip()
        if not ready.startswith("READY"):
            fail(f"rpc process did not come up: {ready!r}")
        port = int(ready.split("service=")[1].split()[0])

        fac = TransactionFactory(suite)
        sender = suite.signature_impl.generate_keypair(secret=0x7E1EBB)
        tx = fac.create_signed(
            sender,
            chain_id="chain0",
            group_id="group0",
            block_limit=500,
            nonce="split-trace-0",
            to=DAG_TRANSFER_ADDRESS,
            input=codec.encode_call("userAdd(string,uint256)", "sp", 1),
        )
        body = json.dumps(
            {
                "jsonrpc": "2.0",
                "id": 1,
                "method": "sendTransaction",
                "params": ["group0", "node0", to_hex(tx.encode())],
            }
        ).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}",
            data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            result = json.loads(resp.read())
            if "result" not in result:
                fail(f"sendTransaction over the split failed: {result}")
            tx_hash = result["result"]["transactionHash"]
        if not node.sealer.seal_and_submit() or node.block_number() != 1:
            fail("split chain did not commit the block")
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/trace/tx/{tx_hash}", timeout=60
        ) as resp:
            doc = json.loads(resp.read())
        if not doc.get("found"):
            fail("/trace/tx did not find the submitted tx")
        stages = {s["name"] for s in doc.get("stages", ())}
        lifecycle = {
            "rpc.forward", "rpc.request", "txpool.submit",
            "txpool.pool_wait", "seal", "pbft.pre_prepare", "pbft.prepare",
            "pbft.commit", "pbft.checkpoint", "scheduler.execute_block",
            "scheduler.2pc_prepare", "scheduler.2pc_commit",
            "scheduler.commit_block",
        }
        covered = stages & lifecycle
        if len(covered) < 5:
            fail(f"stitched trace covers only {sorted(covered)}")
        procs = doc.get("processes", 0)
        if procs < 2:
            fail(f"stitched trace spans {procs} process(es), expected >= 2")
        # the device observatory over the SAME split: the RPC process
        # forwards /device to the node core's facade (ISSUE 13)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/device", timeout=60
        ) as resp:
            dev = json.loads(resp.read())
        if "ledger" not in dev or "phase_ms" not in dev:
            fail(f"/device over the split missing ledger/phase_ms: {dev}")
        print(
            f"split trace ok: {len(covered)} lifecycle stages across "
            f"{procs} processes, dominant={doc.get('dominant')}; "
            f"/device served {len(dev['phase_ms'])} op(s)"
        )
    finally:
        proc.terminate()
        proc.wait(timeout=10)
        facade.stop()
        storage_svc.stop()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--txs", type=int, default=96)
    ap.add_argument("--block-cap", type=int, default=32)
    args = ap.parse_args()
    run_chain(args.txs, args.block_cap)
    check_http()
    check_split_trace_tx()
    print("PASS: telemetry layer live end to end")
    return 0


if __name__ == "__main__":
    sys.exit(main())

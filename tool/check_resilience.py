#!/usr/bin/env python
"""Resilience smoke check: boot a Pro/Max-style split (sharded storage
services + remote executor fleet + consensus node core + HTTP front), run
it through a canned fault plan — one executor flap and one shard flap — and
assert the block pipeline keeps committing while `GET /health` transitions
degraded -> ok on each recovery (ISSUE 2 acceptance).

Runnable locally and from CI (next to tool/check_telemetry.py)::

    python tool/check_resilience.py

Exit 0 on success, 1 with a named failure otherwise.
"""

from __future__ import annotations

import json
import os
import sys
import urllib.error
import urllib.request

# same environment shaping as tool/check_telemetry.py: small compile
# buckets, shared persistent XLA cache, CPU pin (correctness smoke)
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from fisco_bcos_tpu.utils.jaxenv import pin_cpu  # noqa: E402

# a CPU smoke: CPU platform, fast-compile XLA flags, the test suite's 32-lane
# batch bucket and the shared compile cache
pin_cpu()


def fail(msg: str) -> None:
    print(f"FAIL: {msg}")
    raise SystemExit(1)


def get_health(port: int) -> tuple[int, dict]:
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=10
        ) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:  # 503 = degraded, still JSON
        return e.code, json.loads(e.read())


def main() -> int:
    from fisco_bcos_tpu.codec.abi import ABICodec
    from fisco_bcos_tpu.crypto.suite import ecdsa_suite
    from fisco_bcos_tpu.executor import TransactionExecutor
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig
    from fisco_bcos_tpu.node import Node, NodeConfig
    from fisco_bcos_tpu.protocol.transaction import TransactionFactory
    from fisco_bcos_tpu.resilience import (
        HEALTH,
        FaultPlan,
        clear_fault_plan,
        install_fault_plan,
    )
    from fisco_bcos_tpu.rpc.http_server import RpcHttpServer
    from fisco_bcos_tpu.rpc.jsonrpc import JsonRpcImpl
    from fisco_bcos_tpu.service import StorageService
    from fisco_bcos_tpu.service.executor_service import ExecutorService
    from fisco_bcos_tpu.service.rpc import ServiceRemoteError
    from fisco_bcos_tpu.storage import MemoryStorage
    from fisco_bcos_tpu.storage.distributed import DistributedStorage
    from fisco_bcos_tpu.utils.metrics import REGISTRY, bind_node_metrics

    suite = ecdsa_suite()
    codec = ABICodec(suite.hash)
    HEALTH.reset()

    # -- the split: 2 storage shards, executor registry + 2 executors --------
    shards = [StorageService(MemoryStorage()) for _ in range(2)]
    for s in shards:
        s.start()
    endpoints = ",".join(f"{s.host}:{s.port}" for s in shards)
    kp = suite.signature_impl.generate_keypair(secret=0x5EED)
    node = Node(
        NodeConfig(
            genesis=GenesisConfig(consensus_nodes=[ConsensusNode(kp.pub)]),
            storage_endpoints=endpoints,
            executor_registry="127.0.0.1:0",
            executor_min=0,
        ),
        keypair=kp,
    )
    mgr = node.executor_manager
    executors = []

    def add_executor(name: str) -> None:
        ex = TransactionExecutor(
            DistributedStorage([(s.host, s.port) for s in shards]), suite
        )
        svc = ExecutorService(ex, name=name)
        svc.start()
        svc.register_with(mgr.host, mgr.port, interval=0.2)
        executors.append(svc)

    add_executor("rex0")
    add_executor("rex1")
    mgr.wait_for_executors(2, timeout=15.0)

    http = RpcHttpServer(
        JsonRpcImpl(node), port=0,
        metrics=bind_node_metrics(node), health=HEALTH,
    )
    http.start()

    fac = TransactionFactory(suite)
    sender = suite.signature_impl.generate_keypair(secret=0x51E7)
    seq = [0]

    def seal_block(tag: str, n: int = 3) -> None:
        txs = [
            fac.create_signed(
                sender, chain_id="chain0", group_id="group0",
                block_limit=500, nonce=f"{tag}-{seq[0]}-{i}",
                to=DAG_TRANSFER_ADDRESS,
                input=codec.encode_call("userAdd(string,uint256)", f"{tag}{i}", 1),
            )
            for i in range(n)
        ]
        seq[0] += 1
        rs = node.txpool.submit_batch(txs)
        bad = sum(1 for r in rs if r.status != 0)
        if bad:
            fail(f"{bad}/{n} txs rejected at admission ({tag})")
        if not node.sealer.seal_and_submit():
            fail(f"seal_and_submit failed ({tag})")

    try:
        # -- healthy baseline ------------------------------------------------
        seal_block("base")
        if node.block_number() != 1:
            fail(f"baseline block not committed (height {node.block_number()})")
        code, body = get_health(http.port)
        if code != 200 or body["status"] != "ok":
            fail(f"healthy split reports {code} {body}")
        print(f"baseline ok: height 1, /health ok ({sorted(body['components'])})")

        # -- executor flap ---------------------------------------------------
        executors[1].stop()  # kill one executor process
        seal_block("exflap")  # first attempt fails -> term switch -> survivor
        if node.block_number() != 2:
            fail("block did not commit after executor kill")
        code, body = get_health(http.port)
        # a fleet WITH survivors is a serving degradation: 200 + JSON
        # detail (503 would evict a node that just committed a block)
        if code != 200 or body["status"] != "degraded":
            fail(f"/health did not report executor flap as degraded: {code} {body}")
        if body["components"]["executor-fleet"]["status"] != "degraded":
            fail(f"executor-fleet component not degraded: {body}")
        print("executor flap ok: block committed on survivor, /health degraded")

        add_executor("rex2")  # replacement joins -> fleet recovers
        mgr.wait_for_executors(2, timeout=15.0)
        code, body = get_health(http.port)
        if code != 200 or body["status"] != "ok":
            fail(f"/health did not recover after executor rejoin: {code} {body}")
        print("executor recovery ok: /health degraded -> ok")

        # -- shard flap (the canned fault plan, env-spec grammar) ------------
        spec = f"seed=5;kill@send:{shards[1].port}/,count=8"
        install_fault_plan(FaultPlan.from_spec(spec))
        try:
            for i in range(16):
                node.storage.get_row("t_probe", b"p%02d" % i)
        except ServiceRemoteError:
            pass
        else:
            fail("fault plan did not break shard traffic")
        code, body = get_health(http.port)
        # a lost shard blocks 2PC commits: CRITICAL -> 503, pull the node
        if code != 503 or body["status"] != "critical":
            fail(f"/health did not report shard flap as critical: {code} {body}")
        if body["components"]["storage"]["status"] != "degraded":
            fail(f"storage component not degraded: {body}")
        print(f"shard flap ok: plan {spec!r} broke shard 1, /health critical")

        # the plan's count exhausts (the flap ends); traffic heals
        clear_fault_plan()
        for i in range(4):
            node.storage.get_row("t_probe", b"h%02d" % i)
        code, body = get_health(http.port)
        if code != 200 or body["status"] != "ok":
            fail(f"/health did not recover after shard heal: {code} {body}")

        seal_block("postflap")
        if node.block_number() != 3:
            fail("block did not commit after shard flap healed")
        print("shard recovery ok: /health degraded -> ok, block committed")

        # -- metrics surface -------------------------------------------------
        rendered = REGISTRY.render()
        for needle in (
            'fisco_component_health{component="executor-fleet"} 1',
            'fisco_component_health{component="storage"} 1',
            'fisco_component_degraded_total{component="executor-fleet"}',
            'fisco_component_degraded_total{component="storage"}',
        ):
            if needle not in rendered:
                fail(f"metric missing from /metrics: {needle}")
        print("metrics ok: component health gauges + degraded counters exported")
    finally:
        clear_fault_plan()
        http.stop()
        for svc in executors:
            svc.stop()
        if mgr is not None:
            mgr.stop()
        for s in shards:
            s.stop()

    print("PASS: split survives executor + shard flap; /health tracks both")
    return 0


if __name__ == "__main__":
    sys.exit(main())

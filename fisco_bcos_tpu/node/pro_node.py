"""Pro-mode node core: consensus + txpool + scheduler as ONE process whose
gateway, RPC front door, and storage live in OTHER processes.

Reference: the fisco-bcos-tars-service deployment form — a BcosNodeService
(PBFT/txpool/scheduler core) wired over tars to GatewayService, RpcService
and the storage layer; libinitializer/ProNodeInitializer.cpp. This
entrypoint assembles the same split from this framework's parts:

    [gateway svc]  ◀─service RPC─  FrontEndpoint ┐
    [storage svc]  ◀─RemoteStorage (N shards)────┤ node core (this process)
    [rpc svc]      ─▶ RpcFacade  ◀───────────────┘

Usage::

    python -m fisco_bcos_tpu.node.pro_node -g config.genesis \
        --key conf/node.key --gateway 127.0.0.1:41000 \
        --storage 127.0.0.1:42000[,...] [--facade-port N] [--db chain.db]

Prints ``READY facade=<port>`` once serving; SIGTERM/SIGINT stops cleanly.
"""

from __future__ import annotations

# The node core owns the chain's device crypto plane: unlike the pure-IO
# gateway/rpc/storage services (pinned to CPU in service/__main__.py), it
# takes whatever accelerator JAX's default platform resolves to — one node
# core per chip. JAX_PLATFORMS=cpu in the environment keeps it off the chip
# (tests, subprocess fixtures).
import argparse
import signal
import sys
import threading


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="fisco-bcos-tpu-pro-node", description=__doc__)
    ap.add_argument("-g", "--genesis", default="config.genesis")
    ap.add_argument("--key", default="conf/node.key")
    ap.add_argument("--gateway", required=True, help="gateway service host:port")
    ap.add_argument(
        "--storage", default="", help="storage service endpoints h:p[,h:p...]"
    )
    ap.add_argument("--db", default="", help="local sqlite path (no storage svc)")
    ap.add_argument("--facade-port", type=int, default=0)
    ap.add_argument("--sealer-interval", type=float, default=0.2)
    ap.add_argument("--warmup", type=int, default=0, metavar="B")
    ap.add_argument("--sm", action="store_true", help="SM crypto suite")
    ap.add_argument(
        "--executor-registry-port", type=int, default=-1, metavar="PORT",
        help="Max form: host an executor registry on this port and use the "
        "remote executor fleet instead of the in-process executor",
    )
    ap.add_argument(
        "--executors", type=int, default=1,
        help="Max form: executors to wait for at boot",
    )
    args = ap.parse_args(argv)

    from ..crypto.suite import ecdsa_suite, sm_suite
    from ..node import Node, NodeConfig
    from ..node.runtime import NodeRuntime
    from ..rpc import JsonRpcImpl
    from ..service import FrontEndpoint, RemoteGateway, RpcFacade
    from ..tool.config import load_genesis, load_keypair
    from ..utils.jaxenv import configure_compile_cache
    from ..utils.log import get_logger

    log = get_logger("pro-node")
    configure_compile_cache()
    genesis = load_genesis(args.genesis)
    suite = sm_suite() if args.sm else ecdsa_suite()
    kp = load_keypair(args.key, suite)

    cfg = NodeConfig(
        chain_id=genesis.chain_id,
        group_id=genesis.group_id,
        sm_crypto=args.sm,
        db_path=args.db or ":memory:",
        storage_endpoints=args.storage,
        executor_registry=(
            f"127.0.0.1:{args.executor_registry_port}"
            if args.executor_registry_port >= 0
            else ""
        ),
        executor_min=args.executors,
        genesis=genesis,
    )
    node = Node(cfg, keypair=kp)
    if node.executor_manager is not None:
        print(
            f"REGISTRY port={node.executor_manager.port}", flush=True
        )

    # gateway-as-a-process: outbound frames go to the gateway service,
    # inbound ones come back through our FrontEndpoint server
    ep = FrontEndpoint(node.front)
    ep.start()
    gw_host, gw_port = args.gateway.rsplit(":", 1)
    rgw = RemoteGateway(gw_host, int(gw_port))
    node.front.set_gateway(rgw)
    rgw.register_front(ep.host, ep.port)

    if args.warmup:
        node.warmup(batch_sizes=(args.warmup,))

    # split-mode telemetry: the node core binds its metrics + tracer +
    # degraded-mode registry into the facade; the RPC process serves them
    # at GET /metrics, /trace and /health
    from ..observability import TRACER
    from ..resilience import HEALTH
    from ..utils.metrics import bind_node_metrics

    facade = RpcFacade(
        JsonRpcImpl(node),
        port=args.facade_port,
        metrics=bind_node_metrics(node),
        tracer=TRACER,
        health=HEALTH,
        fleet=node.fleet,
    )
    facade.start()

    runtime = NodeRuntime(node, sealer_interval=args.sealer_interval)
    runtime.start()

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_a: stop.set())
    log.info(
        "pro node core %s up: gateway=%s facade=%d storage=%s",
        node.node_id.hex()[:16],
        args.gateway,
        facade.port,
        args.storage or args.db or ":memory:",
    )
    print(f"READY facade={facade.port} front={ep.port}", flush=True)
    stop.wait()
    runtime.stop()
    facade.stop()
    ep.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())

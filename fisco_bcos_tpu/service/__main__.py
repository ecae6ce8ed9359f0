"""Service process entrypoints — the Pro-mode service binaries.

Reference: fisco-bcos-tars-service/{GatewayService/GatewayServiceApp,
RpcService/RpcServiceApp} — the gateway and RPC front door each run as
their own OS process, serving node cores over service RPC.

    python -m fisco_bcos_tpu.service gateway --node-id <hex> \
        [--service-port N] [--p2p-port N] [--peers h:p,...]
    python -m fisco_bcos_tpu.service rpc --facade h:p [--port N]
    python -m fisco_bcos_tpu.service storage [--db path.db] [--port N]
    python -m fisco_bcos_tpu.service executor --storage h:p [--port N]

Each prints one ``READY key=port ...`` line once listening (port 0 resolves
to a kernel-assigned port), then serves until SIGTERM/SIGINT.
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading


def main(argv: list[str] | None = None) -> int:
    # these are pure-IO processes: hold jax to the CPU before anything
    # initialises a backend — the chip belongs to the node core (one process
    # per chip)
    import jax

    jax.config.update("jax_platforms", "cpu")

    ap = argparse.ArgumentParser(prog="fisco-bcos-tpu-service", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    g = sub.add_parser("gateway", help="P2P gateway process")
    g.add_argument("--node-id", required=True, help="node id (hex, 64 bytes)")
    g.add_argument("--service-port", type=int, default=0)
    g.add_argument("--p2p-port", type=int, default=0)
    g.add_argument("--peers", default="", help="comma-separated host:port dials")
    r = sub.add_parser("rpc", help="JSON-RPC front-door process")
    r.add_argument("--facade", required=True, help="node RpcFacade host:port")
    r.add_argument("--port", type=int, default=0)
    s = sub.add_parser("storage", help="storage backend process")
    s.add_argument("--db", default="", help="sqlite path; empty = in-memory")
    s.add_argument("--port", type=int, default=0)
    e = sub.add_parser("executor", help="transaction executor process")
    e.add_argument("--storage", required=True, help="storage service host:port")
    e.add_argument("--port", type=int, default=0)
    e.add_argument("--sm", action="store_true", help="SM crypto suite")
    e.add_argument("--name", default="executor")
    e.add_argument(
        "--registry", default="",
        help="Max form: executor-registry host:port to join (heartbeats)",
    )
    args = ap.parse_args(argv)

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_a: stop.set())

    if args.cmd == "gateway":
        from ..gateway.tcp import TcpGateway
        from .gateway_service import GatewayService

        gw = TcpGateway(bytes.fromhex(args.node_id), port=args.p2p_port)
        svc = GatewayService(gw, port=args.service_port)
        svc.start()
        for hp in filter(None, args.peers.split(",")):
            host, port = hp.rsplit(":", 1)
            gw.connect_peer(host, int(port))
        print(f"READY service={svc.port} p2p={gw.port}", flush=True)
        stop.wait()
        svc.stop()
    elif args.cmd == "rpc":
        from .rpc_service import RpcService

        host, port = args.facade.rsplit(":", 1)
        svc = RpcService(host, int(port), port=args.port)
        svc.start()
        print(f"READY service={svc.port}", flush=True)
        stop.wait()
        svc.stop()
    elif args.cmd == "storage":
        from ..storage import MemoryStorage, SQLiteStorage
        from .storage_service import StorageService

        backend = SQLiteStorage(args.db) if args.db else MemoryStorage()
        svc = StorageService(backend, port=args.port)
        svc.start()
        print(f"READY service={svc.port}", flush=True)
        stop.wait()
        svc.stop()
    else:  # executor
        from ..crypto.suite import ecdsa_suite, sm_suite
        from ..executor import TransactionExecutor
        from .executor_service import ExecutorService
        from .storage_service import RemoteStorage

        host, port = args.storage.rsplit(":", 1)
        store = RemoteStorage(host, int(port))
        suite = sm_suite() if args.sm else ecdsa_suite()
        executor = TransactionExecutor(store, suite)
        svc = ExecutorService(executor, name=args.name, port=args.port)
        svc.start()
        if args.registry:
            rhost, rport = args.registry.rsplit(":", 1)
            svc.register_with(rhost, int(rport))
        print(f"READY service={svc.port}", flush=True)
        stop.wait()
        svc.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())

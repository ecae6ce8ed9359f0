"""Air-node entrypoint: ``python -m fisco_bcos_tpu -c config.ini -g config.genesis``.

Reference: fisco-bcos-air/main.cpp:36-70 (signal handlers + AirNodeInitializer
init/start) and libinitializer/Initializer.cpp:121-330 (the wiring itself,
which here lives in node/node.py).  One OS process runs one node: TCP P2P
gateway, JSON-RPC server, and the runtime worker loop.
"""

from __future__ import annotations

import argparse
import logging
import os
import signal
import sys
import threading
import time

from .gateway import TcpGateway
from .node import Node
from .node.runtime import NodeRuntime
from .rpc import RpcHttpServer
from .tool.config import ChainOptions, load_chain_options, load_keypair
from .utils.log import get_logger

_log = get_logger("main")


def _peer_maintainer(gw: TcpGateway, opts: ChainOptions, stop: threading.Event):
    """Keep dialing the static peer list until every address is connected
    (reference: Service::heartBeat reconnect loop, bcos-gateway
    libp2p/Service.cpp).  Dials are cheap; connected peers re-register."""
    own = (opts.p2p_listen_ip, opts.p2p_listen_port)
    addrs = [(p.host, p.port) for p in opts.peers if (p.host, p.port) != own]
    while not stop.is_set():
        if len(gw.peers()) < len(addrs):
            for host, port in addrs:
                if stop.is_set():
                    break
                gw.connect_peer(host, port)
        stop.wait(2.0)


def build_node(opts: ChainOptions):
    """Assemble a live node from ChainOptions: Node + gateway + RPC + runtime.
    Returns (node, gateway, rpc_server, runtime, stop_event)."""
    from .crypto.suite import ecdsa_suite, sm_suite

    suite = sm_suite() if opts.node.sm_crypto else ecdsa_suite()
    kp = load_keypair(opts.private_key_path, suite)
    node = Node(opts.node, keypair=kp)

    srv_ssl = cli_ssl = rpc_ssl = None
    if opts.enable_ssl:
        from .gateway.tls import make_client_context, make_server_context

        if opts.node.sm_crypto:
            missing = [
                p
                for p in (
                    opts.sm_ca_cert,
                    opts.sm_node_cert,
                    opts.sm_node_key,
                    opts.sm_ennode_cert,
                    opts.sm_ennode_key,
                )
                if not os.path.exists(p)
            ]
            if missing:
                # a silent downgrade to standard TLS would leave this node
                # unable to handshake with its SM peers, with nothing in
                # the logs naming the cause — fail loudly at boot instead
                raise FileNotFoundError(
                    f"sm_crypto chain with enable_ssl requires the SM dual "
                    f"certs; missing {missing} (build_chain --sm --ssl "
                    f"writes them)"
                )
            # national-secret transport on the P2P plane: the TLCP-style
            # dual-cert handshake (gateway/sm_tls — the smCertConfig path,
            # ContextBuilder.cpp:65-74). SMTLSContext is wrap_socket/
            # getpeercert duck-compatible, so the gateway code is shared.
            from .gateway import sm_tls

            srv_ssl = cli_ssl = sm_tls.load_context(
                opts.sm_ca_cert,
                opts.sm_node_cert,
                opts.sm_node_key,
                opts.sm_ennode_cert,
                opts.sm_ennode_key,
            )
        else:
            srv_ssl = make_server_context(opts.ca_cert, opts.node_cert, opts.node_key)
            cli_ssl = make_client_context(opts.ca_cert, opts.node_cert, opts.node_key)
        # RPC stays standard server-TLS (SDK clients speak stdlib ssl)
        rpc_ssl = make_server_context(
            opts.ca_cert, opts.node_cert, opts.node_key, require_client_cert=False
        )
    gw = TcpGateway(
        kp.pub,
        host=opts.p2p_listen_ip,
        port=opts.p2p_listen_port,
        ssl_context=srv_ssl,
        client_ssl_context=cli_ssl,
    )
    gw.connect(node.front)
    from .observability import TRACER, profiler
    from .observability.critical_path import trace_tx
    from .observability.device import device_doc
    from .observability.pipeline import pipeline_doc
    from .resilience import HEALTH
    from .rpc.group_manager import GroupManager, MultiGroupRpc
    from .utils.metrics import bind_node_metrics

    # group-managed RPC surface (bcos-rpc groupmgr): one group today, but
    # getGroupList/getGroupInfoList aggregate and requests route by group
    manager = GroupManager()
    impl = manager.add_node(node)
    fleet = node.fleet
    server = RpcHttpServer(
        MultiGroupRpc(manager, default_group=opts.node.group_id),
        host=opts.rpc_listen_ip,
        port=opts.rpc_listen_port,
        ssl_context=rpc_ssl,
        metrics=bind_node_metrics(node),
        tracer=TRACER,
        health=HEALTH,
        trace_tx=trace_tx,
        pipeline=pipeline_doc,
        profile=profiler.profile,
        device=device_doc,
        fleet=fleet.fleet_doc if fleet is not None else None,
        round_doc=fleet.round_forensics if fleet is not None else None,
        rounds=fleet.rounds_forensics if fleet is not None else None,
    )
    ws = None
    if opts.ws_listen_port:
        from .rpc.event_sub import EventSubEngine
        from .rpc.ws_server import WsService

        ws = WsService(
            impl,
            event_engine=EventSubEngine(node.ledger, node.suite),
            amop=node.amop,
            host=opts.rpc_listen_ip,
            port=opts.ws_listen_port,
            ssl_context=rpc_ssl,
        )
        node.scheduler.on_committed.append(ws.on_block_committed)

    runtime = NodeRuntime(
        node,
        sealer_interval=opts.sealer_interval,
        consensus_timeout=opts.consensus_timeout,
        sync_interval=opts.sync_interval,
    )
    stop = threading.Event()
    return node, gw, server, ws, runtime, stop


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="fisco-bcos-tpu", description=__doc__)
    ap.add_argument("-c", "--config", default="config.ini")
    ap.add_argument("-g", "--genesis", default="config.genesis")
    ap.add_argument(
        "--warmup",
        type=int,
        default=0,
        metavar="B",
        help="pre-compile admission kernels for batch bucket B before serving",
    )
    args = ap.parse_args(argv)

    opts = load_chain_options(args.config, args.genesis)
    logging.basicConfig(
        level=getattr(logging, opts.log_level.upper(), logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
    )

    from .utils.jaxenv import configure_compile_cache

    cache_dir = configure_compile_cache()
    node, gw, server, ws, runtime, stop = build_node(opts)
    _log.info(
        "node %s | chain %s group %s | p2p %s:%d rpc %s:%d | sealer=%s | "
        "compile cache %s",
        node.node_id.hex()[:16],
        opts.node.chain_id,
        opts.node.group_id,
        opts.p2p_listen_ip,
        gw.port,
        opts.rpc_listen_ip,
        opts.rpc_listen_port,
        node.is_sealer(),
        cache_dir,
    )

    if args.warmup:
        node.warmup(batch_sizes=(args.warmup,))

    gw.start()
    dialer = threading.Thread(
        target=_peer_maintainer, args=(gw, opts, stop), name="peer-dial", daemon=True
    )
    dialer.start()
    server.start()
    if ws is not None:
        ws.start()
    runtime.start()

    def _shutdown(signum, frame):
        _log.info("signal %d: shutting down", signum)
        stop.set()

    signal.signal(signal.SIGTERM, _shutdown)
    signal.signal(signal.SIGINT, _shutdown)
    # black box (ISSUE 16): a SIGTERM'd node leaves flight_<node>.json
    # behind — installed over _shutdown so the chain runs flush-then-stop
    from .observability.flight import install_signal_flush

    install_signal_flush(lambda: node.engine.crash_scope or node.node_id.hex()[:8])
    try:
        while not stop.is_set():
            time.sleep(0.2)
    finally:
        runtime.stop()
        if ws is not None:
            ws.stop()
        server.stop()
        gw.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())

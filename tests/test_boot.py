"""Bootable-chain tests: build_chain generator, config loading, TLS handshake
gating, and a real 4-OS-process chain reaching consensus over TCP + RPC.

Reference behaviors: tools/BcosAirBuilder/build_chain.sh (deployment
generation), fisco-bcos-air/main.cpp (node boot), bcos-gateway TLS peer
gating (libnetwork/Host.cpp SSL handshake).
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from fisco_bcos_tpu.codec.abi import ABICodec
from fisco_bcos_tpu.crypto.suite import ecdsa_suite
from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
from fisco_bcos_tpu.front.front import FrontService
from fisco_bcos_tpu.gateway import TcpGateway
from fisco_bcos_tpu.gateway.tls import (
    generate_chain_ca,
    issue_node_cert,
    make_client_context,
    make_server_context,
)
from fisco_bcos_tpu.protocol.transaction import TransactionFactory
from fisco_bcos_tpu.tool.build_chain import build_chain
from fisco_bcos_tpu.tool.config import load_chain_options, load_keypair
from fisco_bcos_tpu.utils.bytesutil import to_hex

SUITE = ecdsa_suite()
CODEC = ABICodec(SUITE.hash)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def wait_until(cond, timeout, interval=0.25):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# Config + builder units (fast)
# ---------------------------------------------------------------------------


def test_build_chain_and_config_roundtrip(tmp_path):
    dirs = build_chain(str(tmp_path / "nodes"), 3, p2p_base=31300, rpc_base=21200)
    assert len(dirs) == 3
    opts = load_chain_options(
        os.path.join(dirs[1], "config.ini"), os.path.join(dirs[1], "config.genesis")
    )
    assert opts.p2p_listen_port == 31301 and opts.rpc_listen_port == 21201
    assert len(opts.peers) == 3 and len(opts.node.genesis.consensus_nodes) == 3
    assert opts.node.db_path.endswith("state.db")
    kp = load_keypair(opts.private_key_path, SUITE)
    assert kp.pub == opts.node.genesis.consensus_nodes[1].node_id
    # nodeid file matches the keypair
    with open(os.path.join(dirs[1], "conf", "node.nodeid")) as f:
        assert f.read().strip() == kp.pub.hex()


def test_genesis_rejects_bad_node_line(tmp_path):
    from fisco_bcos_tpu.tool.config import load_genesis

    p = tmp_path / "config.genesis"
    p.write_text("[consensus]\nnode.0=nothex:1\n")
    with pytest.raises(ValueError):
        load_genesis(str(p))


# ---------------------------------------------------------------------------
# TLS peer gating (in-process gateways, no node stack)
# ---------------------------------------------------------------------------


def _tls_gateway(ca_dir, node_dir, cn, node_id, cert_node_id=None):
    ca_crt = os.path.join(ca_dir, "ca.crt")
    ca_key = os.path.join(ca_dir, "ca.key")
    crt, key = issue_node_cert(
        ca_crt, ca_key, node_dir, cn,
        node_id=node_id if cert_node_id is None else cert_node_id,
    )
    return TcpGateway(
        node_id,
        ssl_context=make_server_context(ca_crt, crt, key),
        client_ssl_context=make_client_context(ca_crt, crt, key),
    )


def test_tls_gateway_accepts_chain_ca_rejects_foreign(tmp_path):
    ca_a = str(tmp_path / "caA")
    ca_b = str(tmp_path / "caB")
    generate_chain_ca(ca_a)
    generate_chain_ca(ca_b)

    gw1 = _tls_gateway(ca_a, str(tmp_path / "n1"), "n1", b"\x01" * 64)
    gw2 = _tls_gateway(ca_a, str(tmp_path / "n2"), "n2", b"\x02" * 64)
    gw3 = _tls_gateway(ca_b, str(tmp_path / "n3"), "n3", b"\x03" * 64)
    f1, f2, f3 = (FrontService(g.node_id) for g in (gw1, gw2, gw3))
    got = []
    f2.register_module(9999, lambda src, payload: got.append((src, payload)))
    try:
        for gw, fr in ((gw1, f1), (gw2, f2), (gw3, f3)):
            gw.connect(fr)
            gw.start()
        # same-CA peers handshake and exchange a frame
        assert gw1.connect_peer(gw2.host, gw2.port)
        assert wait_until(lambda: len(gw1.peers()) == 1, 5)
        f1.send_message(9999, gw2.node_id, b"hello-tls")
        assert wait_until(lambda: got, 5)
        assert got[0] == (gw1.node_id, b"hello-tls")
        # wrong-CA dialer is rejected by the handshake
        assert not gw3.connect_peer(gw1.host, gw1.port)
        time.sleep(0.3)
        assert gw3.node_id not in gw1.peers()
    finally:
        for gw in (gw1, gw2, gw3):
            gw.stop()


def test_tls_gateway_rejects_impersonated_node_id(tmp_path):
    """A chain-CA cert holder claiming ANOTHER node's identity must not
    enter the peer registry: the handshake id is checked against the
    node-id pin the CA wrote into the certificate (ADVICE r2: id/cert
    binding; reference Host.cpp derives the id from the cert)."""
    ca = str(tmp_path / "ca")
    generate_chain_ca(ca)
    victim_id = b"\x11" * 64
    gw1 = _tls_gateway(ca, str(tmp_path / "n1"), "n1", b"\x01" * 64)
    # insider: valid chain-CA cert pinned to its OWN id, but the gateway
    # claims the victim's id in its handshake frames
    evil = _tls_gateway(
        ca, str(tmp_path / "evil"), "evil", victim_id, cert_node_id=b"\x66" * 64
    )
    f1, fe = FrontService(gw1.node_id), FrontService(evil.node_id)
    try:
        gw1.connect(f1)
        gw1.start()
        evil.connect(fe)
        evil.start()
        evil.connect_peer(gw1.host, gw1.port)
        time.sleep(0.5)
        assert victim_id not in gw1.peers()
        # an honest pinned peer with the same CA still connects
        gw2 = _tls_gateway(ca, str(tmp_path / "n2"), "n2", b"\x22" * 64)
        f2 = FrontService(gw2.node_id)
        gw2.connect(f2)
        gw2.start()
        try:
            assert gw2.connect_peer(gw1.host, gw1.port)
            assert wait_until(lambda: gw2.node_id in gw1.peers(), 5)
        finally:
            gw2.stop()
    finally:
        gw1.stop()
        evil.stop()


# ---------------------------------------------------------------------------
# Full 4-process chain (the build_chain.sh + main.cpp end-to-end)
# ---------------------------------------------------------------------------


def _rpc(port, method, *params, timeout=5):
    req = {"jsonrpc": "2.0", "id": 1, "method": method, "params": list(params)}
    r = urllib.request.urlopen(
        urllib.request.Request(
            f"http://127.0.0.1:{port}",
            data=json.dumps(req).encode(),
            headers={"Content-Type": "application/json"},
        ),
        timeout=timeout,
    )
    return json.loads(r.read())


def _rpc_up(port):
    try:
        return _rpc(port, "getBlockNumber")["result"] >= 0
    except Exception:
        return False


_BOOT = (
    "import fisco_bcos_tpu.__main__ as m\n"
    "m.main(['-c', 'config.ini', '-g', 'config.genesis'])\n"
)


@pytest.mark.slow
def test_four_process_chain(tmp_path):
    n = 4
    ports = free_ports(2 * n)
    pairs = [(ports[2 * i], ports[2 * i + 1]) for i in range(n)]
    dirs = build_chain(str(tmp_path / "nodes"), n, ports=pairs)
    for d in dirs:
        # first-compile stalls must not trigger view-change churn on this
        # 1-core host; production keeps the tight default
        cfg = os.path.join(d, "config.ini")
        text = open(cfg).read().replace(
            "consensus_timeout=3.0", "consensus_timeout=600.0"
        )
        open(cfg, "w").write(text)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    procs = []

    def spawn(d):
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _BOOT],
                cwd=d,
                env=env,
                stdout=open(os.path.join(d, "node.log"), "w"),
                stderr=subprocess.STDOUT,
            )
        )

    try:
        rpc_ports = [rpc for _, rpc in pairs]
        # stagger: node0 boots alone first so it fills the persistent XLA
        # compile cache; the other three then load instead of re-compiling
        # (4 concurrent compiles on a 1-core host blow every budget)
        spawn(dirs[0])
        assert wait_until(lambda: _rpc_up(rpc_ports[0]), 300), "node0 not up"
        for d in dirs[1:]:
            spawn(d)
        assert wait_until(
            lambda: all(_rpc_up(p) for p in rpc_ports), 300
        ), "nodes did not serve RPC in time"

        fac = TransactionFactory(SUITE)
        kp = SUITE.signature_impl.generate_keypair(secret=0xB007)
        txs = [
            fac.create_signed(
                kp,
                chain_id="chain0",
                group_id="group0",
                block_limit=500,
                nonce=f"boot-{i}",
                to=DAG_TRANSFER_ADDRESS,
                input=CODEC.encode_call("userAdd(string,uint256)", f"boot{i}", 7),
            )
            for i in range(2)
        ]
        for tx in txs:
            resp = _rpc(
                rpc_ports[0], "sendTransaction", "group0", "", to_hex(tx.encode()),
                timeout=60,
            )
            assert "result" in resp, resp

        def heights():
            out = []
            for p in rpc_ports:
                try:
                    out.append(_rpc(p, "getBlockNumber")["result"])
                except Exception:
                    out.append(-1)
            return out

        # quorum first: consensus is live once 3 of 4 commit (a straggler
        # still tracing XLA programs on this 1-core host is not a
        # consensus failure)...
        assert wait_until(
            lambda: sum(1 for h in heights() if h >= 1) >= 3, 600
        ), heights()
        # ...and the straggler must catch up via block sync within grace
        assert wait_until(lambda: all(h >= 1 for h in heights()), 420), heights()
        # same block hash everywhere (consensus, not 4 solo chains)
        h1 = [
            _rpc(p, "getBlockHashByNumber", "group0", "", 1)["result"]
            for p in rpc_ports
        ]
        assert len(set(h1)) == 1, h1
    finally:
        for p in procs:
            try:
                p.send_signal(signal.SIGTERM)
            except OSError:
                pass
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def test_build_node_selects_sm_transport(tmp_path):
    """An sm_crypto + enable_ssl chain must boot its gateway on the
    SMTLSContext (never the stdlib ssl context), and a missing SM cert is
    a hard boot error, not a silent downgrade to standard TLS."""
    from fisco_bcos_tpu.__main__ import build_node
    from fisco_bcos_tpu.gateway.sm_tls import SMTLSContext

    dirs = build_chain(out_dir=str(tmp_path), count=1, sm=True, ssl=True,
                       ports=[(0, 0, 0)])
    opts = load_chain_options(
        os.path.join(dirs[0], "config.ini"), os.path.join(dirs[0], "config.genesis")
    )
    opts.rpc_listen_port = 0
    node, gw, server, ws, runtime, stop = build_node(opts)
    try:
        assert isinstance(gw._ssl, SMTLSContext)
        assert gw._cli_ssl is gw._ssl
    finally:
        gw.stop()
        server.stop()

    # hard-fail leg: delete the sign cert and boot again
    os.remove(opts.sm_node_cert)
    with pytest.raises(FileNotFoundError, match="SM dual"):
        build_node(opts)


# -- GET /storage is gone: a dashboard that still asks gets a plain 404 -------


def _air_front_door(tmp_path):
    """The Air node's RPC server as the entry point wires it."""
    from fisco_bcos_tpu.__main__ import build_node

    dirs = build_chain(out_dir=str(tmp_path), count=1, ports=[(0, 0, 0)])
    opts = load_chain_options(
        os.path.join(dirs[0], "config.ini"), os.path.join(dirs[0], "config.genesis")
    )
    opts.rpc_listen_port = 0
    _node, gw, server, _ws, _runtime, _stop = build_node(opts)
    server.start()
    return server.port, [server.stop, gw.stop]


def _pro_front_door(_tmp_path):
    """The Pro split's RPC front door over the node core's facade."""
    from fisco_bcos_tpu.service.rpc_service import RpcFacade, RpcService

    facade = RpcFacade(impl=None)
    facade.start()
    rpc = RpcService(facade.host, facade.port)
    rpc.start()
    return rpc.port, [rpc.stop, facade.stop]


def _get(port: int, path: str) -> tuple[int, bytes]:
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10
        ) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


@pytest.mark.parametrize("front_door", [_air_front_door, _pro_front_door],
                         ids=["air", "pro-split"])
def test_get_storage_answers_as_any_unknown_path_does(front_door, tmp_path):
    port, stops = front_door(tmp_path)
    try:
        assert _get(port, "/health")[0] in (200, 503), "the server answers"
        unknown = _get(port, "/no-such-document")
        assert unknown[0] == 404
        assert _get(port, "/storage") == unknown
        assert _get(port, "/storage?last=8") == unknown
    finally:
        for stop in stops:
            stop()

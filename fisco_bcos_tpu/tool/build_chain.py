"""Chain deployment generator — the build_chain.sh analog.

Reference: tools/BcosAirBuilder/build_chain.sh (1,962 lines: chain CA + node
certs, node keys, config.ini/config.genesis per node, start/stop scripts).
Usage::

    python -m fisco_bcos_tpu.tool.build_chain -l 127.0.0.1:4 -o nodes \
        [--sm] [--ssl] [-p 30300,20200]

emits::

    nodes/ca/{ca.crt,ca.key}                (with --ssl)
    nodes/node<i>/config.ini
    nodes/node<i>/config.genesis
    nodes/node<i>/conf/{node.key,node.nodeid[,ssl.crt,ssl.key,ca.crt]}
    nodes/node<i>/start.sh  nodes/{start_all,stop_all}.sh
"""

from __future__ import annotations

import argparse
import os
import shutil
import stat
import sys

from ..utils.jaxenv import CHECKOUT


def _genesis_text(nodeids: list[str], chain_id: str, group_id: str) -> str:
    nodes = "\n".join(
        f"    node.{i}={nid}:1" for i, nid in enumerate(nodeids)
    )
    return f"""[chain]
    chain_id={chain_id}
    group_id={group_id}

[consensus]
    consensus_type=pbft
    block_tx_count_limit=1000
    leader_period=1
{nodes}

[tx]
    gas_limit=3000000000

[executor]
    is_wasm=false

[version]
    compatibility_version=1
"""


def _config_text(
    host: str,
    p2p_port: int,
    rpc_port: int,
    ws_port: int,
    peers: list[tuple[str, int]],
    sm: bool,
    ssl: bool,
) -> str:
    peer_lines = "\n".join(
        f"    node.{i}={h}:{p}" for i, (h, p) in enumerate(peers)
    )
    return f"""[chain]
    sm_crypto={'true' if sm else 'false'}

[security]
    private_key_path=conf/node.key

[cert]
    enable_ssl={'true' if ssl else 'false'}
    ca_cert=conf/ca.crt
    node_cert=conf/ssl.crt
    node_key=conf/ssl.key
    sm_ca_cert=conf/sm_ca.crt
    sm_node_cert=conf/sm_ssl.crt
    sm_node_key=conf/sm_ssl.key
    sm_ennode_cert=conf/sm_enssl.crt
    sm_ennode_key=conf/sm_enssl.key

[rpc]
    listen_ip={host}
    listen_port={rpc_port}
    ws_port={ws_port}

[p2p]
    listen_ip={host}
    listen_port={p2p_port}
{peer_lines}

[consensus]
    consensus_timeout=3.0
    sealer_interval=0.05

[sync]
    sync_interval=0.5

[storage]
    data_path=data

[txpool]
    limit=135000
    block_limit=600

[log]
    level=info
"""


# The package is not installed: every generated script exports PYTHONPATH
# for the checkout that generated it. JAX_COMPILATION_CACHE_DIR is left to
# the environment (unset: <checkout>/.jax_cache, utils/jaxenv.py).
_START_SH = """#!/bin/bash
cd "$(dirname "$0")"
export PYTHONPATH="{checkout}${{PYTHONPATH:+:$PYTHONPATH}}"
nohup {python} -m fisco_bcos_tpu -c config.ini -g config.genesis \\
    >> node.log 2>&1 &
echo $! > node.pid
echo "started node (pid $(cat node.pid))"
"""

# One process per chip: a node process takes the accelerator JAX finds and
# holds it until it exits. On one chip, run ONE node process (a 4-node
# committee on one chip is the in-process mapping, see README).
_START_ALL_NOTE = (
    "# one node process per chip: each ./nodeN/start.sh below claims the\n"
    "# accelerator JAX finds. Set JAX_PLATFORMS=cpu for every node that has\n"
    "# no chip of its own.\n"
)

_STOP_SH = """#!/bin/bash
cd "$(dirname "$0")"
[ -f node.pid ] && kill "$(cat node.pid)" 2>/dev/null && rm -f node.pid
"""


def _write_exec(path: str, text: str) -> None:
    with open(path, "w") as f:
        f.write(text)
    os.chmod(path, os.stat(path).st_mode | stat.S_IXUSR | stat.S_IXGRP)


def build_chain(
    out_dir: str,
    count: int,
    host: str = "127.0.0.1",
    p2p_base: int = 30300,
    rpc_base: int = 20200,
    sm: bool = False,
    ssl: bool = False,
    chain_id: str = "chain0",
    group_id: str = "group0",
    ports: list[tuple[int, int]] | None = None,
) -> list[str]:
    """Generate `count` node directories under out_dir; returns their paths.
    `ports` overrides the (p2p, rpc) pair per node (tests pick free ports)."""
    from ..crypto.suite import ecdsa_suite, sm_suite

    from .config import save_keypair

    suite = sm_suite() if sm else ecdsa_suite()
    os.makedirs(out_dir, exist_ok=True)

    if ports is None:
        # third member = websocket channel (event-sub/AMOP push)
        ports = [(p2p_base + i, rpc_base + i, rpc_base + 500 + i) for i in range(count)]
    ports = [p if len(p) == 3 else (p[0], p[1], p[1] + 500) for p in ports]
    keypairs = [suite.signature_impl.generate_keypair() for _ in range(count)]
    nodeids = [kp.pub.hex() for kp in keypairs]
    genesis = _genesis_text(nodeids, chain_id, group_id)
    peers = [(host, p[0]) for p in ports]

    ca_crt = ca_key = sm_ca = None
    if ssl:
        from ..gateway.tls import generate_chain_ca

        ca_crt, ca_key = generate_chain_ca(os.path.join(out_dir, "ca"))
        if sm:
            # national-secret transport: a second, SM2 chain CA issuing the
            # TLCP dual pairs (reference build_chain.sh generates the sm_*
            # cert tree alongside the RSA/EC one when -s is set)
            from ..gateway.sm_tls import generate_sm_chain_ca

            sm_ca = generate_sm_chain_ca(os.path.join(out_dir, "ca"))

    node_dirs = []
    for i in range(count):
        ndir = os.path.join(out_dir, f"node{i}")
        conf = os.path.join(ndir, "conf")
        os.makedirs(conf, exist_ok=True)
        p2p_port, rpc_port, ws_port = ports[i]
        with open(os.path.join(ndir, "config.genesis"), "w") as f:
            f.write(genesis)
        with open(os.path.join(ndir, "config.ini"), "w") as f:
            f.write(_config_text(host, p2p_port, rpc_port, ws_port, peers, sm, ssl))
        save_keypair(os.path.join(conf, "node.key"), keypairs[i])
        if ssl:
            from ..gateway.tls import issue_node_cert

            issue_node_cert(
                ca_crt, ca_key, conf, f"node{i}", hosts=[host],
                node_id=keypairs[i].pub,
            )
            shutil.copy(ca_crt, os.path.join(conf, "ca.crt"))
            if sm_ca is not None:
                from ..gateway.sm_tls import issue_sm_node_certs

                issue_sm_node_certs(
                    sm_ca, conf, f"node{i}", node_id=keypairs[i].pub
                )
        _write_exec(
            os.path.join(ndir, "start.sh"),
            _START_SH.format(python=sys.executable, checkout=CHECKOUT),
        )
        _write_exec(os.path.join(ndir, "stop.sh"), _STOP_SH)
        node_dirs.append(ndir)

    _write_exec(
        os.path.join(out_dir, "start_all.sh"),
        "#!/bin/bash\n" + _START_ALL_NOTE + "cd \"$(dirname \"$0\")\"\n"
        + "".join(f"./node{i}/start.sh\n" for i in range(count)),
    )
    _write_exec(
        os.path.join(out_dir, "stop_all.sh"),
        "#!/bin/bash\ncd \"$(dirname \"$0\")\"\n"
        + "".join(f"./node{i}/stop.sh\n" for i in range(count)),
    )
    return node_dirs


# ---------------------------------------------------------------------------
# Pro-mode deployer (the BcosBuilder/ProNodeInitializer analog)
# ---------------------------------------------------------------------------

_PRO_SVC_SH = """#!/bin/bash
cd "$(dirname "$0")"
export PYTHONPATH="{checkout}${{PYTHONPATH:+:$PYTHONPATH}}"
nohup {python} -m {module} {args} > {name}.log 2>&1 &
echo $! > {name}.pid
"""



def _pro_svc_sh(module: str, args: str, name: str) -> str:
    return _PRO_SVC_SH.format(
        python=sys.executable, checkout=CHECKOUT, module=module, args=args,
        name=name,
    )


_PRO_STOP_SH = """#!/bin/bash
cd "$(dirname "$0")"
for pid in rpc.pid core.pid gateway.pid storage.pid; do
    [ -f "$pid" ] && kill "$(cat "$pid")" 2>/dev/null && rm -f "$pid"
done
exit 0
"""

# Max nodes additionally run N executor services; *.pid catches them all
_MAX_STOP_SH = """#!/bin/bash
cd "$(dirname "$0")"
for pid in *.pid; do
    [ -f "$pid" ] && kill "$(cat "$pid")" 2>/dev/null && rm -f "$pid"
done
exit 0
"""


def build_pro_chain(
    out_dir: str,
    count: int,
    host: str = "127.0.0.1",
    port_base: int = 40000,
    sm: bool = False,
    chain_id: str = "chain0",
    group_id: str = "group0",
) -> list[str]:
    """Generate a Pro-topology deployment: per node a storage service, a
    gateway service, the node core (pro_node) and an RPC front-door process,
    each with its own start script and a deterministic port block.

    Reference: tools/BcosBuilder (the python deployer that renders per-
    service config/start artifacts for the tars Pro deployment form) +
    fisco-bcos-tars-service process layout. Port block per node i:
    base+10i = storage, +1 gateway service, +2 p2p, +3 node facade,
    +4 rpc http.
    """
    from ..crypto.suite import ecdsa_suite, sm_suite

    from .config import save_keypair

    suite = sm_suite() if sm else ecdsa_suite()
    os.makedirs(out_dir, exist_ok=True)
    keypairs = [suite.signature_impl.generate_keypair() for _ in range(count)]
    genesis = _genesis_text([kp.pub.hex() for kp in keypairs], chain_id, group_id)

    def ports(i):
        b = port_base + 10 * i
        return {"storage": b, "gwsvc": b + 1, "p2p": b + 2, "facade": b + 3, "rpc": b + 4}

    node_dirs = []
    for i in range(count):
        ndir = os.path.join(out_dir, f"node{i}")
        conf = os.path.join(ndir, "conf")
        os.makedirs(conf, exist_ok=True)
        p = ports(i)
        with open(os.path.join(ndir, "config.genesis"), "w") as f:
            f.write(genesis)
        save_keypair(os.path.join(conf, "node.key"), keypairs[i])
        peers = ",".join(
            f"{host}:{ports(j)['p2p']}" for j in range(count) if j != i
        )
        sm_flag = " --sm" if sm else ""
        svcs = [
            (
                "storage",
                "fisco_bcos_tpu.service",
                f"storage --db chain.db --port {p['storage']}",
            ),
            (
                "gateway",
                "fisco_bcos_tpu.service",
                f"gateway --node-id {keypairs[i].pub.hex()} "
                f"--service-port {p['gwsvc']} --p2p-port {p['p2p']}"
                + (f" --peers {peers}" if peers else ""),
            ),
            (
                "core",
                "fisco_bcos_tpu.node.pro_node",
                f"-g config.genesis --key conf/node.key "
                f"--gateway {host}:{p['gwsvc']} --storage {host}:{p['storage']} "
                f"--facade-port {p['facade']}" + sm_flag,
            ),
            (
                "rpc",
                "fisco_bcos_tpu.service",
                f"rpc --facade {host}:{p['facade']} --port {p['rpc']}",
            ),
        ]
        for name, module, svc_args in svcs:
            _write_exec(
                os.path.join(ndir, f"start_{name}.sh"),
                _pro_svc_sh(module, svc_args, name),
            )
        _write_exec(
            os.path.join(ndir, "start.sh"),
            "#!/bin/bash\ncd \"$(dirname \"$0\")\"\n"
            "./start_storage.sh\nsleep 0.5\n./start_gateway.sh\nsleep 0.5\n"
            "./start_core.sh\nsleep 1\n./start_rpc.sh\n",
        )
        _write_exec(os.path.join(ndir, "stop.sh"), _PRO_STOP_SH)
        node_dirs.append(ndir)

    _write_exec(
        os.path.join(out_dir, "start_all.sh"),
        "#!/bin/bash\ncd \"$(dirname \"$0\")\"\n"
        + "".join(f"./node{i}/start.sh\n" for i in range(count)),
    )
    _write_exec(
        os.path.join(out_dir, "stop_all.sh"),
        "#!/bin/bash\ncd \"$(dirname \"$0\")\"\n"
        + "".join(f"./node{i}/stop.sh\n" for i in range(count)),
    )
    return node_dirs


def build_max_chain(
    out_dir: str,
    count: int,
    executors: int = 2,
    host: str = "127.0.0.1",
    port_base: int = 40000,
    sm: bool = False,
    chain_id: str = "chain0",
    group_id: str = "group0",
) -> list[str]:
    """Generate a Max-topology deployment: ONE shared storage service (the
    TiKV analog), and per consensus node a gateway service, the node core
    hosting an executor registry, an RPC front door, and a fleet of
    ``executors`` stateless executor processes that register with the core
    and heartbeat (killing one mid-block is survivable — the scheduler
    term-switches and re-executes on the survivors).

    Reference: tools/BcosBuilder max profile + fisco-bcos-tars-service
    (every subsystem its own service; TarsRemoteExecutorManager discovery).
    Port block: base = shared storage; per node i at base+20(i+1):
    +0 gateway svc, +1 p2p, +2 facade, +3 rpc, +4 registry,
    +5.. executor services.
    """
    from ..crypto.suite import ecdsa_suite, sm_suite

    from .config import save_keypair

    if not 1 <= executors <= 14:
        # the per-node port block is 20 wide (5 fixed + executor slots);
        # more executors would collide with the next node's block
        raise ValueError(f"max mode supports 1..14 executors per node, got {executors}")
    suite = sm_suite() if sm else ecdsa_suite()
    os.makedirs(out_dir, exist_ok=True)
    keypairs = [suite.signature_impl.generate_keypair() for _ in range(count)]
    genesis = _genesis_text([kp.pub.hex() for kp in keypairs], chain_id, group_id)
    sm_flag = " --sm" if sm else ""

    storage_port = port_base
    _write_exec(
        os.path.join(out_dir, "start_storage.sh"),
        _pro_svc_sh(
            "fisco_bcos_tpu.service",
            f"storage --db max_chain.db --port {storage_port}",
            "storage",
        ),
    )

    def ports(i):
        b = port_base + 20 * (i + 1)
        return {
            "gwsvc": b, "p2p": b + 1, "facade": b + 2, "rpc": b + 3,
            "registry": b + 4, "exec0": b + 5,
        }

    node_dirs = []
    for i in range(count):
        ndir = os.path.join(out_dir, f"node{i}")
        conf = os.path.join(ndir, "conf")
        os.makedirs(conf, exist_ok=True)
        p = ports(i)
        with open(os.path.join(ndir, "config.genesis"), "w") as f:
            f.write(genesis)
        save_keypair(os.path.join(conf, "node.key"), keypairs[i])
        peers = ",".join(
            f"{host}:{ports(j)['p2p']}" for j in range(count) if j != i
        )
        svcs = [
            (
                "gateway",
                "fisco_bcos_tpu.service",
                f"gateway --node-id {keypairs[i].pub.hex()} "
                f"--service-port {p['gwsvc']} --p2p-port {p['p2p']}"
                + (f" --peers {peers}" if peers else ""),
            ),
            (
                "core",
                "fisco_bcos_tpu.node.pro_node",
                f"-g config.genesis --key conf/node.key "
                f"--gateway {host}:{p['gwsvc']} --storage {host}:{storage_port} "
                f"--facade-port {p['facade']} "
                f"--executor-registry-port {p['registry']} "
                f"--executors {executors}" + sm_flag,
            ),
            (
                "rpc",
                "fisco_bcos_tpu.service",
                f"rpc --facade {host}:{p['facade']} --port {p['rpc']}",
            ),
        ]
        for e in range(executors):
            svcs.append(
                (
                    f"executor{e}",
                    "fisco_bcos_tpu.service",
                    f"executor --storage {host}:{storage_port} "
                    f"--port {p['exec0'] + e} --name node{i}-executor{e} "
                    f"--registry {host}:{p['registry']}" + sm_flag,
                )
            )
        for name, module, svc_args in svcs:
            _write_exec(
                os.path.join(ndir, f"start_{name}.sh"),
                _pro_svc_sh(module, svc_args, name),
            )
        exec_starts = "".join(
            f"./start_executor{e}.sh\n" for e in range(executors)
        )
        _write_exec(
            os.path.join(ndir, "start.sh"),
            "#!/bin/bash\ncd \"$(dirname \"$0\")\"\n"
            "./start_gateway.sh\nsleep 0.5\n"
            + exec_starts
            + "sleep 0.5\n./start_core.sh\nsleep 1\n./start_rpc.sh\n",
        )
        _write_exec(os.path.join(ndir, "stop.sh"), _MAX_STOP_SH)
        node_dirs.append(ndir)

    _write_exec(
        os.path.join(out_dir, "start_all.sh"),
        "#!/bin/bash\ncd \"$(dirname \"$0\")\"\n./start_storage.sh\nsleep 1\n"
        + "".join(f"./node{i}/start.sh\n" for i in range(count)),
    )
    _write_exec(
        os.path.join(out_dir, "stop_all.sh"),
        "#!/bin/bash\ncd \"$(dirname \"$0\")\"\n"
        + "".join(f"./node{i}/stop.sh\n" for i in range(count))
        + "pkill -f 'fisco_bcos_tpu.service storage' 2>/dev/null\ntrue\n",
    )
    return node_dirs


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="build_chain", description=__doc__)
    ap.add_argument("-l", "--listen", default="127.0.0.1:4", help="host:count")
    ap.add_argument("-o", "--output", default="nodes")
    ap.add_argument("-p", "--ports", default="30300,20200", help="p2p_base,rpc_base")
    ap.add_argument("--sm", action="store_true", help="SM2/SM3 national crypto")
    ap.add_argument("--ssl", action="store_true", help="mutual TLS on P2P + RPC")
    ap.add_argument("--chain-id", default="chain0")
    ap.add_argument("--group-id", default="group0")
    ap.add_argument(
        "--mode",
        choices=("air", "pro", "max"),
        default="air",
        help="air = one process per node; pro = storage/gateway/core/rpc "
        "as separate service processes per node (BcosBuilder analog); "
        "max = shared storage + per-node executor fleet with registry "
        "discovery and failover",
    )
    ap.add_argument(
        "--executors", type=int, default=2,
        help="max mode: executor services per node",
    )
    args = ap.parse_args(argv)

    host, count = args.listen.rsplit(":", 1)
    if args.mode == "max":
        if args.ssl:
            ap.error("--ssl is not supported with --mode max")
        dirs = build_max_chain(
            args.output,
            int(count),
            executors=args.executors,
            host=host,
            port_base=int(args.ports.split(",")[0]),
            sm=args.sm,
            chain_id=args.chain_id,
            group_id=args.group_id,
        )
        print(f"generated {len(dirs)} max node groups under {args.output}/")
        return 0
    if args.mode == "pro":
        if args.ssl:
            ap.error(
                "--ssl is not supported with --mode pro yet; the pro "
                "service mesh runs plaintext service RPC on localhost"
            )
        dirs = build_pro_chain(
            args.output,
            int(count),
            host=host,
            port_base=int(args.ports.split(",")[0]),
            sm=args.sm,
            chain_id=args.chain_id,
            group_id=args.group_id,
        )
        print(f"generated {len(dirs)} pro node groups under {args.output}/")
        return 0
    p2p_base, rpc_base = (int(x) for x in args.ports.split(","))
    dirs = build_chain(
        args.output,
        int(count),
        host=host,
        p2p_base=p2p_base,
        rpc_base=rpc_base,
        sm=args.sm,
        ssl=args.ssl,
        chain_id=args.chain_id,
        group_id=args.group_id,
    )
    print(f"generated {len(dirs)} nodes under {args.output}/")
    return 0


if __name__ == "__main__":
    sys.exit(main())

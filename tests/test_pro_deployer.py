"""Pro-mode deployer (BcosBuilder analog): generated artifacts boot a chain.

Reference: tools/BcosBuilder + fisco-bcos-tars-service process layout;
libinitializer ProNodeInitializer wiring.
"""

import json
import os
import random
import subprocess
import sys
import time
import urllib.request

import jax

jax.config.update("jax_platforms", "cpu")

from fisco_bcos_tpu.tool.build_chain import build_pro_chain  # noqa: E402


def test_generated_layout(tmp_path):
    dirs = build_pro_chain(str(tmp_path), 2, port_base=47500)
    assert len(dirs) == 2
    for i, d in enumerate(dirs):
        for f in (
            "config.genesis",
            "conf/node.key",
            "start_storage.sh",
            "start_gateway.sh",
            "start_core.sh",
            "start_rpc.sh",
            "start.sh",
            "stop.sh",
        ):
            assert os.path.exists(os.path.join(d, f)), f
        core = open(os.path.join(d, "start_core.sh")).read()
        assert f"--facade-port {47500 + 10 * i + 3}" in core
        gw = open(os.path.join(d, "start_gateway.sh")).read()
        assert f"--p2p-port {47500 + 10 * i + 2}" in gw
    # node1's gateway dials node0's p2p port
    gw1 = open(os.path.join(dirs[1], "start_gateway.sh")).read()
    assert "--peers 127.0.0.1:47502" in gw1
    assert os.path.exists(tmp_path / "start_all.sh")


def _wait_ready(proc, deadline=90):
    """Read lines until READY; keep draining afterwards on a thread."""
    import threading

    ready = {}
    t0 = time.monotonic()
    for line in proc.stdout:
        if line.startswith("READY"):
            ready.update(
                {
                    k: int(v)
                    for k, v in (kv.split("=") for kv in line.strip().split()[1:])
                }
            )
            break
        if time.monotonic() - t0 > deadline:
            break

    def drain():
        for _ in proc.stdout:
            pass

    threading.Thread(target=drain, daemon=True).start()
    return ready


def test_pro_deployment_boots_and_commits(tmp_path):
    base = random.randint(4400, 5900) * 10
    (ndir,) = build_pro_chain(str(tmp_path), 1, port_base=base)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.setdefault("FISCO_TEST_BUCKET", "32")
    # the node core follows the platform default (the chip in production);
    # this is a CPU test
    env["JAX_PLATFORMS"] = "cpu"
    # services run from the node dir (chain.db lands there); the package
    # still resolves from the repo
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    def spawn(args):
        return subprocess.Popen(
            [sys.executable, "-m", *args],
            cwd=ndir,
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )

    p = {
        "storage": base,
        "gwsvc": base + 1,
        "p2p": base + 2,
        "facade": base + 3,
        "rpc": base + 4,
    }
    with open(os.path.join(ndir, "conf", "node.key")) as f:
        node_id = None  # node id comes from the key; gateway takes it as arg
    from fisco_bcos_tpu.crypto.suite import ecdsa_suite
    from fisco_bcos_tpu.tool.config import load_keypair

    kp = load_keypair(os.path.join(ndir, "conf", "node.key"), ecdsa_suite())

    procs = []
    try:
        st = spawn(
            ["fisco_bcos_tpu.service", "storage", "--db", "chain.db", "--port", str(p["storage"])]
        )
        procs.append(st)
        assert _wait_ready(st), "storage did not come up"
        gw = spawn(
            [
                "fisco_bcos_tpu.service", "gateway",
                "--node-id", kp.pub.hex(),
                "--service-port", str(p["gwsvc"]), "--p2p-port", str(p["p2p"]),
            ]
        )
        procs.append(gw)
        assert _wait_ready(gw), "gateway did not come up"
        core = spawn(
            [
                "fisco_bcos_tpu.node.pro_node",
                "-g", "config.genesis", "--key", "conf/node.key",
                "--gateway", f"127.0.0.1:{p['gwsvc']}",
                "--storage", f"127.0.0.1:{p['storage']}",
                "--facade-port", str(p["facade"]),
                "--warmup", env["FISCO_TEST_BUCKET"],
                "--sealer-interval", "0.05",
            ]
        )
        procs.append(core)
        assert _wait_ready(core, deadline=600), "node core did not come up"
        rpc_p = spawn(
            [
                "fisco_bcos_tpu.service", "rpc",
                "--facade", f"127.0.0.1:{p['facade']}", "--port", str(p["rpc"]),
            ]
        )
        procs.append(rpc_p)
        assert _wait_ready(rpc_p), "rpc did not come up"

        def rpc(method, *params):
            req = {"jsonrpc": "2.0", "id": 1, "method": method, "params": list(params)}
            r = urllib.request.urlopen(
                urllib.request.Request(
                    f"http://127.0.0.1:{p['rpc']}",
                    data=json.dumps(req).encode(),
                    headers={"Content-Type": "application/json"},
                ),
                timeout=30,
            )
            return json.loads(r.read())

        assert rpc("getBlockNumber")["result"] == 0

        from fisco_bcos_tpu.codec.abi import ABICodec
        from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
        from fisco_bcos_tpu.protocol.transaction import TransactionFactory

        suite = ecdsa_suite()
        codec = ABICodec(suite.hash)
        fac = TransactionFactory(suite)
        sender = suite.signature_impl.generate_keypair(secret=0xDE9107)
        tx = fac.create_signed(
            sender, chain_id="chain0", group_id="group0", block_limit=500,
            nonce="deploy-1", to=DAG_TRANSFER_ADDRESS,
            input=codec.encode_call("userAdd(string,uint256)", "deployed", 3),
        )
        resp = rpc("sendTransaction", "group0", "", tx.encode().hex())
        assert "error" not in resp, resp

        deadline = time.monotonic() + 120
        head = 0
        while time.monotonic() < deadline:
            head = rpc("getBlockNumber")["result"]
            if head >= 1:
                break
            time.sleep(0.3)
        assert head >= 1, "chain never committed through the pro split"
        # the durable backend belongs to the storage process
        assert os.path.exists(os.path.join(ndir, "chain.db"))
    finally:
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()

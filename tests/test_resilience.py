"""Resilience subsystem: fault plans, retry/deadline, breaker, /health.

Reference analogs: tars proxy reconnect/backoff, TarsRemoteExecutorManager's
liveness machinery, TiKVStorage's switch handler — here unified as
resilience/{faults,retry,breaker}.py and wired through service/rpc.py,
gateway/tcp.py and the telemetry surface (ISSUE 2).
"""

import jax

jax.config.update("jax_platforms", "cpu")

import json  # noqa: E402
import socket  # noqa: E402
import struct  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402
import urllib.error  # noqa: E402
import urllib.request  # noqa: E402

import pytest  # noqa: E402

from fisco_bcos_tpu.resilience import (  # noqa: E402
    HEALTH,
    CircuitBreaker,
    Deadline,
    DeadlineExceeded,
    FaultPlan,
    HealthRegistry,
    RetryPolicy,
    clear_fault_plan,
    install_fault_plan,
    is_idempotent,
)
from fisco_bcos_tpu.service.rpc import (  # noqa: E402
    BadFrame,
    FrameTooLarge,
    ServiceClient,
    ServiceConnectionError,
    ServiceServer,
)


@pytest.fixture(autouse=True)
def _clean_plan():
    clear_fault_plan()
    yield
    clear_fault_plan()


# -- fault plan ---------------------------------------------------------------


def test_fault_plan_seeded_determinism():
    def pattern(seed):
        plan = FaultPlan(seed=seed).drop("recv", "x", p=0.5)
        return [plan.on_recv("x", b"m") is None for _ in range(32)]

    assert pattern(7) == pattern(7)  # same seed -> same fault sequence
    assert pattern(7) != pattern(8)  # (2^-32 false-failure odds)


def test_fault_plan_spec_parsing():
    plan = FaultPlan.from_spec(
        "seed=42;drop@recv:42001,p=0.5,count=3;refuse@connect:executor;"
        "kill@send:*,after=10;delay@recv:shard,ms=5"
    )
    assert plan.seed == 42
    actions = [(r.action, r.site, r.target) for r in plan._rules]
    assert actions == [
        ("drop", "recv", "42001"),
        ("refuse", "connect", "executor"),
        ("kill", "send", "*"),
        ("delay", "recv", "shard"),
    ]
    assert plan._rules[0].count == 3 and plan._rules[2].after == 10
    with pytest.raises(ValueError):
        FaultPlan.from_spec("explode@send:*")


def test_fault_rule_count_and_after():
    plan = FaultPlan().kill_after(2, "send", "t", count=1)
    # first two sends pass untouched, third kills, fourth passes (count=1)
    assert plan.on_send("t", b"a") == ([b"a"], False)
    assert plan.on_send("t", b"b") == ([b"b"], False)
    assert plan.on_send("t", b"c") == ([], True)
    assert plan.on_send("t", b"d") == ([b"d"], False)
    assert plan.injected == 1


# -- retry / deadline ---------------------------------------------------------


def test_retry_policy_deterministic_backoff():
    a = RetryPolicy(max_attempts=5, base_delay=0.1, max_delay=1.0, seed=3)
    b = RetryPolicy(max_attempts=5, base_delay=0.1, max_delay=1.0, seed=3)
    assert [a.delay(i) for i in range(5)] == [b.delay(i) for i in range(5)]
    # capped: the uncapped 4th step would be 0.8..1.0*1.25
    assert all(d <= 1.0 * 1.25 for d in (a.delay(i) for i in range(8)))


def test_retry_policy_retries_then_raises():
    calls = []

    def flaky():
        calls.append(1)
        raise ConnectionResetError("nope")

    pol = RetryPolicy(max_attempts=3, base_delay=0.001, jitter=0)
    with pytest.raises(ConnectionResetError):
        pol.run(flaky)
    assert len(calls) == 3
    # non-classified errors never retry
    calls.clear()

    def bad():
        calls.append(1)
        raise ValueError("data")

    with pytest.raises(ValueError):
        pol.run(bad)
    assert len(calls) == 1


def test_deadline_bounds_retry_loop():
    pol = RetryPolicy(max_attempts=50, base_delay=0.05, jitter=0)
    t0 = time.monotonic()
    with pytest.raises((ConnectionResetError, DeadlineExceeded)):
        pol.run(
            lambda: (_ for _ in ()).throw(ConnectionResetError()),
            deadline=Deadline.after(0.25),
        )
    assert time.monotonic() - t0 < 2.0  # nowhere near 50 attempts
    # DeadlineExceeded is an OSError: existing transport handling absorbs it
    assert issubclass(DeadlineExceeded, OSError)


def test_idempotency_classification():
    assert is_idempotent("get_row") and is_idempotent("prepare")
    assert not is_idempotent("execute_transactions")
    assert not is_idempotent("never-registered-method")


# -- circuit breaker / health -------------------------------------------------


def test_breaker_trips_and_half_opens():
    reg = HealthRegistry()
    br = CircuitBreaker("dev", failure_threshold=2, reset_timeout=0.15, registry=reg)
    assert br.allow() and br.state == "closed"
    br.record_failure("x")
    assert br.state == "closed" and reg.status("dev") == "unknown"
    br.record_failure("y")
    assert br.state == "open" and not br.allow()
    assert reg.status("dev") == "degraded" and reg.overall() == "critical"
    time.sleep(0.2)
    assert br.state == "half-open"
    assert br.allow()  # the single probe
    assert not br.allow()  # second caller waits
    br.record_success()
    assert br.state == "closed" and reg.status("dev") == "ok"
    assert reg.overall() == "ok"


def test_breaker_call_with_fallback():
    reg = HealthRegistry()
    br = CircuitBreaker("p", failure_threshold=1, reset_timeout=60, registry=reg)

    def boom():
        raise RuntimeError("dead path")

    assert br.call(boom, fallback=lambda: "host") == "host"
    assert br.state == "open"
    # open circuit routes straight to the fallback, no boom call
    assert br.call(boom, fallback=lambda: "host2") == "host2"


def test_breaker_probe_released_when_both_paths_fail():
    """Regression: an exception escaping the half-open probe (device AND
    host path both raise — a data error) must free the probe slot, not
    wedge the breaker in half-open forever."""
    from fisco_bcos_tpu.device.dispatch import _device_or_host

    reg = HealthRegistry()
    br = CircuitBreaker("dev2", failure_threshold=1, reset_timeout=0.05, registry=reg)
    br.record_failure("seed")  # open
    time.sleep(0.1)  # cooldown -> half-open

    import fisco_bcos_tpu.device.dispatch as dispatch_mod

    old = dispatch_mod._DEVICE_BREAKER
    dispatch_mod._DEVICE_BREAKER = br
    try:
        def boom(*a):
            raise RuntimeError("path down")

        with pytest.raises(RuntimeError):
            _device_or_host("test", boom, boom)  # both legs fail: data error
        assert br.allow()  # probe slot free again — NOT wedged
        br.release_probe()
        # and an unclassified escape through CircuitBreaker.call too
        time.sleep(0.1)
        with pytest.raises(KeyboardInterrupt):
            br.call(lambda: (_ for _ in ()).throw(KeyboardInterrupt()),
                    classify=(ValueError,))
        assert br.allow()
    finally:
        dispatch_mod._DEVICE_BREAKER = old


def test_health_snapshot_shape():
    reg = HealthRegistry()
    reg.ok("a")
    reg.degrade("b", "lost")  # critical by default
    reg.degrade("c", "slow path", critical=False)
    snap = reg.snapshot()
    assert snap["status"] == "critical"
    assert snap["components"]["b"]["reason"] == "lost"
    assert snap["components"]["c"]["critical"] is False
    js = json.loads(reg.to_json())
    # for_seconds is wall-clock-dependent: strip before the equality check
    for d in (snap, js):
        for comp in d["components"].values():
            comp.pop("for_seconds")
    assert js == snap
    # a non-critical degradation alone reads "degraded", never "critical"
    reg.ok("b")
    assert reg.overall() == "degraded"


# -- service RPC: typed frames, timeouts, retry -------------------------------


def _echo_server():
    s = ServiceServer("resil")
    s.register("echo", lambda p: p)
    s.start()
    return s


def test_frame_too_large_is_typed_and_logged():
    # a rogue "server" that answers any frame with an over-cap header
    lst = socket.socket()
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)

    def serve():
        conn, _ = lst.accept()
        conn.recv(65536)
        conn.sendall(struct.pack("<I", 1 << 31))  # 2 GiB "frame"
        time.sleep(0.5)
        conn.close()

    threading.Thread(target=serve, daemon=True).start()
    c = ServiceClient(*lst.getsockname(), timeout=5)
    with pytest.raises(FrameTooLarge):
        c.call("echo", b"x")
    c.close()
    lst.close()


def test_recv_timeout_is_a_typed_connection_error():
    # a server that accepts and never replies: the recv timeout must turn a
    # wedged call into ServiceConnectionError (was: hang for `timeout`=60s)
    lst = socket.socket()
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    threading.Thread(target=lambda: (lst.accept(), time.sleep(5)), daemon=True).start()
    c = ServiceClient(*lst.getsockname(), timeout=0.3)
    t0 = time.monotonic()
    with pytest.raises(ServiceConnectionError):
        c.call("echo", b"x")
    assert time.monotonic() - t0 < 2.0
    c.close()
    lst.close()


def test_client_retry_heals_refused_connect():
    s = _echo_server()
    s.register("get_row", lambda p: p)
    try:
        # first TWO dials are refused by the plan; the third succeeds. An
        # idempotent call under a RetryPolicy rides through transparently.
        install_fault_plan(FaultPlan(seed=1).refuse_connect(str(s.port), count=2))
        c = ServiceClient(
            s.host, s.port, timeout=5,
            retry=RetryPolicy(max_attempts=3, base_delay=0.01, jitter=0),
        )
        assert c.call("get_row", b"k") == b"k"  # get_row: classified idempotent
        c.close()
    finally:
        s.stop()


def test_non_idempotent_method_never_retries():
    s = _echo_server()
    s.register("execute_transactions", lambda p: p)
    try:
        install_fault_plan(FaultPlan().refuse_connect(str(s.port), count=1))
        c = ServiceClient(
            s.host, s.port, timeout=5,
            retry=RetryPolicy(max_attempts=5, base_delay=0.01, jitter=0),
        )
        with pytest.raises(ServiceConnectionError):
            c.call("execute_transactions", b"tx")
        # the refusal was consumed by the single (non-retried) attempt
        assert c.call("execute_transactions", b"tx") == b"tx"
        c.close()
    finally:
        s.stop()


def test_kill_after_n_messages_then_heal():
    s = _echo_server()
    try:
        c = ServiceClient(s.host, s.port, timeout=5)
        plan = FaultPlan().kill_after(4, "send", str(s.port), count=1)
        install_fault_plan(plan)
        for i in range(2):  # 2 calls = 2 send events, both pass
            assert c.call("echo", b"%d" % i) == b"%d" % i
        with pytest.raises(ServiceConnectionError):
            c.call("echo", b"killed")  # 3rd call = 5th matching event? no:
            # client sends are events 3 (pass) ... the server's replies also
            # match target=port? server scope is "svc:resil:<port>" — yes.
            # events: c1 send, s1 reply, c2 send, s2 reply, c3 send -> kill
        assert plan.injected == 1
        assert c.call("echo", b"healed") == b"healed"  # redial heals
        c.close()
    finally:
        s.stop()


def test_duplicate_fault_desync_is_typed_and_self_heals():
    s = _echo_server()
    try:
        c = ServiceClient(s.host, s.port, timeout=5)
        install_fault_plan(FaultPlan().duplicate("send", f"{s.port}/echo", count=1))
        assert c.call("echo", b"a") == b"a"  # dup executed server-side too
        clear_fault_plan()
        with pytest.raises(BadFrame):
            c.call("echo", b"b")  # stale dup reply: id mismatch, typed
        assert c.call("echo", b"c") == b"c"  # clean redial
        c.close()
    finally:
        s.stop()


def test_truncated_reply_is_bad_frame():
    s = _echo_server()
    try:
        c = ServiceClient(s.host, s.port, timeout=5)
        install_fault_plan(FaultPlan().truncate("recv", f"{s.port}/echo", count=1, keep=3))
        with pytest.raises(BadFrame):
            c.call("echo", b"payload")
        clear_fault_plan()
        assert c.call("echo", b"ok") == b"ok"
        c.close()
    finally:
        s.stop()


def test_zero_overhead_passthrough_no_plan():
    # with no plan installed the wire behavior is byte-identical and the
    # hot path adds one global read: the call simply works
    s = _echo_server()
    try:
        c = ServiceClient(s.host, s.port, timeout=5)
        payload = b"z" * 4096
        assert c.call("echo", payload) == payload
        c.close()
    finally:
        s.stop()


# -- gateway fault hooks ------------------------------------------------------


def test_gateway_connect_refusal_via_plan():
    from fisco_bcos_tpu.gateway.tcp import TcpGateway

    a = TcpGateway(b"\x01" * 64, heartbeat_interval=0)
    b = TcpGateway(b"\x02" * 64, heartbeat_interval=0)
    a.start()
    b.start()
    try:
        install_fault_plan(FaultPlan().refuse_connect(f"gw:{b.host}:{b.port}"))
        assert a.connect_peer(b.host, b.port) is False
        clear_fault_plan()
        assert a.connect_peer(b.host, b.port) is True
        deadline = Deadline.after(5)
        while not a.peers() and not deadline.expired():
            time.sleep(0.02)
        assert b"\x02" * 64 in a.peers()
    finally:
        a.stop()
        b.stop()


# -- /health end to end (in-process and split) --------------------------------


def test_health_endpoint_transitions():
    from fisco_bcos_tpu.rpc.http_server import RpcHttpServer

    reg = HealthRegistry()
    reg.ok("storage")
    srv = RpcHttpServer(impl=None, port=0, health=reg)
    srv.start()
    try:
        url = f"http://127.0.0.1:{srv.port}/health"
        with urllib.request.urlopen(url, timeout=5) as resp:
            body = json.loads(resp.read())
            assert resp.status == 200 and body["status"] == "ok"
        reg.degrade("storage", "shard down")  # critical -> 503
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url, timeout=5)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "critical"
        reg.ok("storage")
        # a non-critical (serving-through-fallback) degradation stays 200:
        # probes must not evict a node that is answering correctly
        reg.degrade("device-crypto", "serving from the host loop", critical=False)
        with urllib.request.urlopen(url, timeout=5) as resp:
            body = json.loads(resp.read())
            assert resp.status == 200 and body["status"] == "degraded"
        reg.ok("device-crypto")
        with urllib.request.urlopen(url, timeout=5) as resp:
            assert json.loads(resp.read())["status"] == "ok"
    finally:
        srv.stop()


def test_split_mode_health_forwarding():
    """Pro split: the node core's registry serves GET /health through the
    RPC process (RpcFacade `health` method -> RemoteTelemetry proxy)."""
    from fisco_bcos_tpu.service.rpc_service import RpcFacade, RpcService

    reg = HealthRegistry()
    reg.degrade("executor-fleet", "flap")  # critical (unit: forwarding)
    facade = RpcFacade(None, port=0, health=reg)
    facade.start()
    svc = RpcService(facade.host, facade.port, port=0)
    svc.start()
    try:
        url = f"http://127.0.0.1:{svc.port}/health"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url, timeout=5)
        body = json.loads(ei.value.read())
        assert ei.value.code == 503
        assert body["components"]["executor-fleet"]["reason"] == "flap"
        reg.ok("executor-fleet", "rejoined")
        with urllib.request.urlopen(url, timeout=5) as resp:
            assert json.loads(resp.read())["status"] == "ok"
    finally:
        svc.stop()
        facade.stop()


def test_split_mode_health_survives_dead_facade():
    from fisco_bcos_tpu.service.rpc_service import RpcFacade, RpcService

    facade = RpcFacade(None, port=0, health=HEALTH)
    facade.start()
    svc = RpcService(facade.host, facade.port, port=0)
    svc.start()
    try:
        facade.stop()  # node core "crashes"
        url = f"http://127.0.0.1:{svc.port}/health"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url, timeout=10)
        body = json.loads(ei.value.read())
        assert ei.value.code == 503
        assert body["components"]["node-core"]["status"] == "degraded"
    finally:
        svc.stop()


# -- corrupt action (ISSUE 6 satellite) ---------------------------------------


def test_corrupt_spec_parsing_and_builder():
    plan = FaultPlan.from_spec("seed=9;corrupt@recv:42001,bits=5,count=2")
    (r,) = plan._rules
    assert r.action == "corrupt" and r.bits == 5 and r.count == 2
    plan2 = FaultPlan(seed=9).corrupt("send", "x", bits=5, count=2)
    (r2,) = plan2._rules
    assert r2.action == "corrupt" and r2.bits == 5


def test_corrupt_bitflips_are_seeded_and_spare_the_header():
    wire = bytes(range(4, 104))  # 4-byte "header" + 96-byte body

    def flipped(seed):
        plan = FaultPlan(seed=seed).corrupt("send", "*", bits=6)
        chunks, kill = plan.on_send("anywhere", wire)
        assert not kill and len(chunks) == 1
        return chunks[0]

    a, b, c = flipped(3), flipped(3), flipped(4)
    assert a == b != c  # deterministic per seed
    assert a != wire  # something actually flipped
    assert a[:4] == wire[:4]  # length header intact: frame still parses
    # exactly <=6 bits differ (xor popcount)
    diff = sum(bin(x ^ y).count("1") for x, y in zip(a, wire))
    assert 0 < diff <= 6


def test_corrupt_reply_rejected_typed_never_crashes():
    from fisco_bcos_tpu.service.rpc import ServiceRemoteError

    s = _echo_server()
    try:
        c = ServiceClient(s.host, s.port, timeout=5)
        assert c.call("echo", b"warm") == b"warm"
        # many trials: wherever the flips land (id, ok flag, length words,
        # payload) the outcome must be a typed error or a decoded reply —
        # anything else (struct.error, MemoryError, hang) is the bug class
        # the corrupt action exists to catch
        for i in range(12):
            install_fault_plan(
                FaultPlan(seed=100 + i).corrupt(
                    "recv", f"{s.port}/echo", count=1, bits=8
                )
            )
            payload = bytes((i + j) & 0xFF for j in range(48))
            try:
                out = c.call("echo", payload)
                assert isinstance(out, bytes)
            except ServiceRemoteError:
                pass  # BadFrame / FrameTooLarge / connection loss: all typed
            clear_fault_plan()
            assert c.call("echo", b"again") == b"again"  # always self-heals
        c.close()
    finally:
        s.stop()


def test_corrupt_request_counted_at_server():
    from fisco_bcos_tpu.service.rpc import ServiceRemoteError
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    s = _echo_server()
    try:
        before = sum(
            REGISTRY.counters_matching("fisco_swallowed_errors_total").values()
        )
        c = ServiceClient(s.host, s.port, timeout=5)
        assert c.call("echo", b"warm") == b"warm"
        # corrupt OUTBOUND requests until the server visibly drops one as
        # undecodable (some flips land in the payload and decode fine)
        hit = False
        for i in range(10):
            install_fault_plan(
                FaultPlan(seed=200 + i).corrupt(
                    "send", f"{s.port}/echo", count=1, bits=10
                )
            )
            try:
                c.call("echo", bytes(range(64)))
            except ServiceRemoteError:
                pass
            clear_fault_plan()
            after = sum(
                REGISTRY.counters_matching(
                    "fisco_swallowed_errors_total"
                ).values()
            )
            if after > before:
                hit = True
                break
            assert c.call("echo", b"sane") == b"sane"
        assert hit, "no corrupt request was ever counted as rejected"
        c.close()
    finally:
        s.stop()

"""JSON-RPC over HTTP (stdlib ThreadingHTTPServer).

Reference transport: bcos-rpc over bcos-boostssl ws/http. HTTP POST with
JSON-RPC 2.0 bodies (single or batch); the ws push channels (AMOP, event
subscription, block notify) ride the amop/event modules.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from ..utils.log import get_logger
from .jsonrpc import JsonRpcImpl

_log = get_logger("rpc-http")


def _accepts_openmetrics(accept: str | None) -> bool:
    """True when the Accept header opts INTO application/openmetrics-text:
    an offer with q=0 is an explicit refusal, not an opt-in."""
    for part in (accept or "").split(","):
        media, _, params = part.partition(";")
        if "openmetrics" not in media:
            continue
        q = 1.0
        for p in params.split(";"):
            k, _, v = p.strip().partition("=")
            if k == "q":
                try:
                    q = float(v)
                except ValueError:
                    q = 0.0
        if q > 0:
            return True
    return False


class RpcHttpServer:
    """`ssl_context` (gateway.tls.make_server_context) upgrades to HTTPS —
    the reference's boostssl TLS RPC channel."""

    def __init__(
        self,
        impl: JsonRpcImpl,
        host: str = "127.0.0.1",
        port: int = 20200,
        ssl_context=None,
        metrics=None,
        tracer=None,
        health=None,
        trace_tx=None,
        pipeline=None,
        profile=None,
        device=None,
        fleet=None,
        round_doc=None,
        rounds=None,
    ):
        self.impl = impl
        # `metrics` needs .render() -> str; `tracer` needs .export_json() ->
        # str; `health` needs .to_json() -> str — satisfied by
        # MetricsRegistry/Tracer/HealthRegistry in-process and by the
        # RemoteTelemetry proxy in the split (Pro/Max) deployment.
        # `trace_tx` (tx-hash hex -> critical-path dict) serves
        # GET /trace/tx/<hash>; `pipeline` (() -> dict) serves the stage
        # occupancy/watermark document at GET /pipeline; `profile`
        # (seconds -> dict) serves the sampling profiler at
        # GET /profile?seconds=N; `device` (() -> dict) serves the device
        # observatory (compile ledger + phase attribution) at GET /device.
        # When omitted, a tracer exposing its own
        # .trace_tx/.pipeline/.profile/.device (RemoteTelemetry) is used.
        self.metrics = metrics
        self.tracer = tracer
        self.health = health
        self.trace_tx = trace_tx or getattr(tracer, "trace_tx", None)
        self.pipeline = pipeline or getattr(tracer, "pipeline", None)
        self.profile = profile or getattr(tracer, "profile", None)
        self.device = device or getattr(tracer, "device", None)
        # fleet observatory (ISSUE 16): `fleet` (() -> dict) merges every
        # peer's telemetry into one cluster doc at GET /fleet; `round_doc`
        # (height -> dict) serves per-round forensics at GET /round/<h>;
        # `rounds` (last -> dict) the recent-rounds sweep at GET /rounds
        self.fleet = fleet or getattr(tracer, "fleet", None)
        self.round_doc = round_doc or getattr(tracer, "round_doc", None)
        self.rounds = rounds or getattr(tracer, "rounds", None)
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self) -> None:  # noqa: N802
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    body = self.rfile.read(length)
                    req = json.loads(body)
                    # strike attribution: this client's IP is the source
                    # the txpool files invalid-signature strikes against
                    from .jsonrpc import client_source

                    with client_source(f"rpc:{self.client_address[0]}"):
                        if isinstance(req, list):
                            resp = [outer.impl.handle(r) for r in req]
                        else:
                            resp = outer.impl.handle(req)
                    data = json.dumps(resp).encode()
                    self.send_response(200)
                except Exception as e:
                    data = json.dumps(
                        {
                            "jsonrpc": "2.0",
                            "id": None,
                            "error": {"code": -32700, "message": f"parse error: {e}"},
                        }
                    ).encode()
                    self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self) -> None:  # noqa: N802 — telemetry scrape
                code = 200
                if self.path == "/metrics" and outer.metrics is not None:
                    # exemplars only under negotiated OpenMetrics — the
                    # classic 0.0.4 text parser rejects the suffix
                    om = _accepts_openmetrics(self.headers.get("Accept"))
                    try:
                        data = outer.metrics.render(openmetrics=om).encode()
                    except TypeError:  # renderer without the kwarg
                        data = outer.metrics.render().encode()
                        om = False
                    if om and not data.strip():
                        # a failed split-mode render returns "" — an empty
                        # body labeled OpenMetrics lacks the mandatory
                        # '# EOF' and fails strict scrapers; serve it as
                        # (empty) classic text instead
                        om = False
                    ctype = (
                        "application/openmetrics-text; version=1.0.0; "
                        "charset=utf-8"
                        if om
                        else "text/plain; version=0.0.4"
                    )
                elif self.path == "/trace" and outer.tracer is not None:
                    # Chrome trace-event JSON — load in Perfetto as-is
                    data = outer.tracer.export_json().encode()
                    ctype = "application/json"
                elif (
                    self.path.startswith("/trace/tx/")
                    and outer.trace_tx is not None
                ):
                    # stitched per-transaction critical path (ISSUE 4):
                    # every lifecycle span sharing the tx's trace set,
                    # ordered, with the dominant stage named
                    doc = outer.trace_tx(
                        self.path.split("?", 1)[0].rsplit("/", 1)[1]
                    )
                    data = json.dumps(doc, default=str).encode()
                    ctype = "application/json"
                    if not doc.get("found"):
                        code = 404
                elif (
                    self.path.split("?", 1)[0] == "/pipeline"
                    and outer.pipeline is not None
                ):
                    # stage occupancy + blocked-on edges + backpressure
                    # watermark timelines (ISSUE 9 pipeline observatory)
                    data = json.dumps(outer.pipeline(), default=str).encode()
                    ctype = "application/json"
                elif (
                    self.path.split("?", 1)[0] == "/device"
                    and outer.device is not None
                ):
                    # device observatory (ISSUE 13): compile ledger with
                    # cold-vs-persistent-cache attribution, per-op phase
                    # totals, memory watermarks, recompile-storm state
                    data = json.dumps(outer.device(), default=str).encode()
                    ctype = "application/json"
                elif (
                    self.path.split("?", 1)[0] == "/profile"
                    and outer.profile is not None
                ):
                    # sampling wall-clock profiler: blocks for ?seconds=N
                    # (server-side clamped) and returns collapsed stacks +
                    # per-function self time
                    from urllib.parse import parse_qs, urlsplit

                    qs = parse_qs(urlsplit(self.path).query)
                    seconds = (qs.get("seconds") or ["2"])[0]
                    doc = outer.profile(seconds)
                    data = json.dumps(doc, default=str).encode()
                    ctype = "application/json"
                    if doc.get("error"):
                        code = 503
                elif (
                    self.path.split("?", 1)[0] == "/fleet"
                    and outer.fleet is not None
                ):
                    # federated cluster document (ISSUE 16): this node pulls
                    # every committee peer's snapshot + round ledger over
                    # the gateway mesh and merges them — unreachable peers
                    # appear as degraded rows, never vanish
                    data = json.dumps(outer.fleet(), default=str).encode()
                    ctype = "application/json"
                elif (
                    self.path.startswith("/round/")
                    and outer.round_doc is not None
                ):
                    # cross-node forensics for one consensus height: aligned
                    # phase spans, per-signer vote arrivals, straggler
                    try:
                        height = int(
                            self.path.split("?", 1)[0].rsplit("/", 1)[1]
                        )
                    except ValueError:
                        self.send_response(404)
                        self.end_headers()
                        return
                    doc = outer.round_doc(height)
                    data = json.dumps(doc, default=str).encode()
                    ctype = "application/json"
                    if not doc.get("found"):
                        code = 404
                elif (
                    self.path.split("?", 1)[0] == "/rounds"
                    and outer.rounds is not None
                ):
                    # recent rounds with skew percentiles; ?last=N bounds it
                    from urllib.parse import parse_qs, urlsplit

                    qs = parse_qs(urlsplit(self.path).query)
                    try:
                        last = int((qs.get("last") or ["32"])[0])
                    except ValueError:
                        last = 32
                    data = json.dumps(
                        outer.rounds(last), default=str
                    ).encode()
                    ctype = "application/json"
                elif self.path == "/health" and outer.health is not None:
                    # degraded-mode registry (resilience.HEALTH or the
                    # split-mode RemoteTelemetry proxy). 503 ONLY on
                    # "critical" (not ready: probes should pull the node);
                    # "degraded" still answers 200 — the node is serving
                    # through fallbacks and the JSON body carries the detail
                    data = outer.health.to_json().encode()
                    ctype = "application/json"
                    try:
                        if json.loads(data).get("status") == "critical":
                            code = 503
                    except ValueError:
                        code = 503
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def log_message(self, fmt, *args):  # quiet
                pass

        self._server = ThreadingHTTPServer((host, port), Handler)
        if ssl_context is not None:
            self._server.socket = ssl_context.wrap_socket(
                self._server.socket, server_side=True
            )
        self.port = self._server.server_address[1]
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="rpc-http", daemon=True
        )
        self._thread.start()
        _log.info("json-rpc listening on %d", self.port)

    def stop(self) -> None:
        if self._thread is not None:
            # shutdown() blocks until serve_forever acknowledges — calling
            # it on a never-started server waits forever
            self._server.shutdown()
        self._server.server_close()
        if self._thread:
            self._thread.join(timeout=5)

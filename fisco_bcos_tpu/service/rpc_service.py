"""RPC service — the JSON-RPC front door as its own process.

Reference: fisco-bcos-tars-service/RpcService (RpcServiceServer.cpp): in the
Pro topology the HTTP/WS listener runs as its own process and forwards every
JSON-RPC request to the node core over service RPC. The node hosts an
`RpcFacade` server wrapping its JsonRpcImpl; the RPC process runs the
standard RpcHttpServer with a forwarding `handle` — transport parsing stays
in the RPC process, chain logic stays in the node.

    client ──HTTP──▶ [rpc process] RpcHttpServer(RemoteJsonRpc) ──RPC──▶
                     [node process] RpcFacade(JsonRpcImpl.handle)
"""

from __future__ import annotations

import json

from ..codec.flat import FlatReader, FlatWriter
from ..utils.log import get_logger
from .rpc import ServiceClient, ServiceServer

_log = get_logger("rpc-svc")


class RpcFacade:
    """Node-side server exposing JsonRpcImpl.handle over service RPC, plus
    the node's telemetry surface (`metrics`/`trace` methods) so the RPC
    process can serve `GET /metrics` and `GET /trace` for the whole split
    deployment — the node core owns the registry and tracer, the RPC
    process only forwards."""

    def __init__(
        self, impl, host: str = "127.0.0.1", port: int = 0, metrics=None,
        tracer=None, health=None, fleet=None,
    ):
        self.impl = impl
        self.metrics = metrics
        self.tracer = tracer
        # degraded-mode registry (resilience.HEALTH shape: .to_json());
        # served to the RPC process for GET /health
        self.health = health
        # fleet observatory (ISSUE 16): the node core owns the FleetService
        # (mesh access + round ledger); the RPC process only forwards
        self.fleet = fleet
        self.server = ServiceServer("rpc-facade", host, port)
        self.server.register("handle", self._handle)
        self.server.register("metrics", self._metrics)
        self.server.register("trace", self._trace)
        self.server.register("trace_tx", self._trace_tx)
        self.server.register("health", self._health)
        self.server.register("pipeline", self._pipeline)
        self.server.register("device", self._device)
        # concurrent: the profiler blocks for seconds reading only
        # sys._current_frames() — under the dispatch lock one /profile
        # would stall every JSON-RPC call on the split
        self.server.register("profile", self._profile, concurrent=True)
        # concurrent: a fleet merge waits out per-peer deadlines against
        # dead peers (seconds) — it must never serialize JSON-RPC traffic
        self.server.register("fleet", self._fleet, concurrent=True)
        self.server.register("round", self._round, concurrent=True)
        self.server.register("rounds", self._rounds, concurrent=True)
        self.host, self.port = self.server.host, self.server.port

    def start(self) -> None:
        self.server.start()

    def stop(self) -> None:
        self.server.stop()

    def _handle(self, payload: bytes) -> bytes:
        req = json.loads(payload)
        return json.dumps(self.impl.handle(req)).encode()

    def _metrics(self, payload: bytes) -> bytes:
        if self.metrics is None:
            return b""
        if payload == b"openmetrics":
            # no silent downgrade: the RPC process labels the response
            # application/openmetrics-text, so a renderer without the
            # kwarg must surface as an error reply, not classic text
            # masquerading as OpenMetrics (no '# EOF', no exemplars)
            return self.metrics.render(openmetrics=True).encode()
        return self.metrics.render().encode()

    def _trace(self, _payload: bytes) -> bytes:
        if self.tracer is None:
            return b'{"traceEvents": []}'
        return self.tracer.export_json().encode()

    def _trace_tx(self, payload: bytes) -> bytes:
        """Raw (un-analyzed) critical-path collection for one tx hash hex:
        the node core owns the tx/block indexes; the RPC process merges its
        OWN ring's spans (the submit root lives there) before analyzing."""
        if self.tracer is None:
            return b'{"found": false, "spans": []}'
        from ..observability import critical_path

        return json.dumps(
            critical_path.collect(payload.decode()), default=str
        ).encode()

    def _health(self, _payload: bytes) -> bytes:
        if self.health is None:
            return b'{"status": "ok", "components": {}}'
        return self.health.to_json().encode()

    def _pipeline(self, _payload: bytes) -> bytes:
        """The node core's stage-occupancy/watermark document — the split
        deployment's GET /pipeline source (the pipeline lives where the
        pipeline workers live)."""
        from ..observability.pipeline import pipeline_doc

        return json.dumps(pipeline_doc(), default=str).encode()

    def _device(self, _payload: bytes) -> bytes:
        """The node core's device-observatory document (compile ledger,
        phase totals, memory watermarks) — the split deployment's
        GET /device source: compiles happen where the DevicePlane lives."""
        from ..observability.device import device_doc

        return json.dumps(device_doc(), default=str).encode()

    def _profile(self, payload: bytes) -> bytes:
        """Sample THIS process (the node core — where the pipeline burns
        its wall time) for the requested seconds. Clamped server-side
        below the telemetry proxy's RPC timeout — the client-side clamp
        in RemoteTelemetry must not be the only guard."""
        from ..observability import profiler

        try:
            seconds = float(payload.decode() or "2")
        except ValueError:
            seconds = 2.0
        return json.dumps(
            profiler.profile(min(seconds, 8.0)), default=str
        ).encode()

    def _fleet(self, _payload: bytes) -> bytes:
        """The merged cluster document — the split deployment's GET /fleet
        source: the node core holds the mesh connection to every peer."""
        if self.fleet is None:
            from ..observability.fleet import DISABLED_DOC

            return json.dumps(DISABLED_DOC).encode()
        return json.dumps(self.fleet.fleet_doc(), default=str).encode()

    def _round(self, payload: bytes) -> bytes:
        if self.fleet is None:
            return b'{"found": false, "reason": "FISCO_FLEET_OBS=0"}'
        try:
            height = int(payload.decode() or "0")
        except ValueError:
            height = 0
        return json.dumps(
            self.fleet.round_forensics(height), default=str
        ).encode()

    def _rounds(self, payload: bytes) -> bytes:
        if self.fleet is None:
            return b'{"rounds": [], "reason": "FISCO_FLEET_OBS=0"}'
        try:
            last = int(payload.decode() or "32")
        except ValueError:
            last = 32
        return json.dumps(
            self.fleet.rounds_forensics(last), default=str
        ).encode()


class RemoteJsonRpc:
    """RPC-process-side `handle` that forwards requests to the node's
    facade — a drop-in for JsonRpcImpl wherever a transport needs one
    (RpcHttpServer, WsService request path)."""

    def __init__(self, host: str, port: int, timeout: float = 120.0):
        self.client = ServiceClient(host, port, timeout)

    def handle(self, request: dict) -> dict:
        try:
            method = request.get("method", "")
            from ..rpc.jsonrpc import TRACED_RPC_METHODS

            if method in TRACED_RPC_METHODS:
                from ..observability import TRACER

                # the split deployment's lifecycle root: opened in the RPC
                # process, continued by the node core via the traceparent
                # the service client injects into the facade call. Read
                # polls stay span-free (same ring-churn guard as
                # JsonRpcImpl.handle).
                with TRACER.span("rpc.forward", method=method):
                    resp = self.client.call(
                        "handle", json.dumps(request).encode()
                    )
            else:
                resp = self.client.call("handle", json.dumps(request).encode())
            return json.loads(resp)
        except Exception as e:
            _log.exception("facade call failed")
            return {
                "jsonrpc": "2.0",
                "id": request.get("id"),
                "error": {"code": -32603, "message": f"node unreachable: {e}"},
            }

    def close(self) -> None:
        self.client.close()


class RemoteTelemetry:
    """RPC-process-side metrics/trace proxy over the node facade — duck-
    compatible with MetricsRegistry.render / Tracer.export_json where
    RpcHttpServer needs them. A facade without the telemetry methods (or an
    unreachable node) degrades to empty output, never a 500. Owns its OWN
    ServiceClient (short timeout): ServiceClient serializes calls on one
    connection lock, so a scrape against a stalled node core must never
    queue JSON-RPC requests behind it (nor the reverse)."""

    def __init__(self, host: str, port: int, timeout: float = 10.0):
        self.client = ServiceClient(host, port, timeout)

    def render(self, openmetrics: bool = False) -> str:
        try:
            return self.client.call(
                "metrics", b"openmetrics" if openmetrics else b""
            ).decode()
        except Exception:
            return ""

    def export_json(self) -> str:
        try:
            return self.client.call("trace").decode()
        except Exception:
            return '{"traceEvents": []}'

    def trace_tx(self, tx_hash_hex: str) -> dict:
        """Stitch one tx's critical path ACROSS the split: the node core's
        collection (its ring + indexes) merged with THIS process's spans —
        the submit root and any rpc-process work belong to the same trace
        but live in this ring, not the node's."""
        from ..observability import critical_path

        try:
            doc = json.loads(
                self.client.call("trace_tx", tx_hash_hex.encode())
            )
        except Exception:
            return {"found": False, "txHash": tx_hash_hex, "spans": []}
        if doc.get("found"):
            trace_ids = {int(t, 16) for t in doc.get("traceIds", ())}
            local = critical_path.local_spans_for(trace_ids, doc.get("block"))
            known = {(s["trace_id"], s["span_id"]) for s in doc["spans"]}
            doc["spans"].extend(
                s for s in local if (s["trace_id"], s["span_id"]) not in known
            )
        return critical_path.analyze(doc)

    def pipeline(self) -> dict:
        """GET /pipeline over the split: the node core owns the stage
        recorder; an unreachable core degrades to an explicit error doc."""
        try:
            return json.loads(self.client.call("pipeline", b""))
        except Exception as e:
            return {
                "enabled": False,
                "error": f"facade unreachable: {e}",
                "stages": {},
                "watermarks": {},
            }

    def device(self) -> dict:
        """GET /device over the split: the node core owns the compile
        ledger; an unreachable core degrades to an explicit error doc."""
        try:
            return json.loads(self.client.call("device", b""))
        except Exception as e:
            return {
                "enabled": False,
                "error": f"facade unreachable: {e}",
                "ledger": [],
                "phase_ms": {},
            }

    def profile(self, seconds=2.0) -> dict:
        """GET /profile over the split — samples the NODE CORE process.
        Clamped below this proxy's RPC timeout so a long profile can never
        read as a dead facade."""
        try:
            seconds = min(float(seconds), 8.0)
        except (TypeError, ValueError):
            seconds = 2.0
        try:
            return json.loads(
                self.client.call("profile", str(seconds).encode())
            )
        except Exception as e:
            return {"error": f"facade unreachable: {e}"}

    def fleet(self) -> dict:
        """GET /fleet over the split: the node core runs the federation
        pull; an unreachable core degrades to an explicit error doc."""
        try:
            return json.loads(self.client.call("fleet", b""))
        except Exception as e:
            return {
                "enabled": False,
                "error": f"facade unreachable: {e}",
                "nodes": {},
            }

    def round_doc(self, height) -> dict:
        """GET /round/<h> over the split — cross-node forensics for one
        consensus height, assembled by the node core."""
        try:
            return json.loads(self.client.call("round", str(int(height)).encode()))
        except Exception as e:
            return {"found": False, "error": f"facade unreachable: {e}"}

    def rounds(self, last=32) -> dict:
        """GET /rounds over the split — recent rounds + skew percentiles."""
        try:
            last = int(last)
        except (TypeError, ValueError):
            last = 32
        try:
            return json.loads(self.client.call("rounds", str(last).encode()))
        except Exception as e:
            return {"rounds": [], "error": f"facade unreachable: {e}"}

    def to_json(self) -> str:
        """Health JSON for GET /health. An unreachable node core IS a
        degraded deployment — report it as such instead of erroring."""
        try:
            return self.client.call("health").decode()
        except Exception as e:
            return json.dumps(
                {
                    "status": "critical",  # no node core = not serving
                    "components": {
                        "node-core": {
                            "status": "degraded",
                            "reason": f"facade unreachable: {e}",
                            "critical": True,
                        }
                    },
                }
            )

    def close(self) -> None:
        self.client.close()


class RpcService:
    """The RPC process: HTTP JSON-RPC listener over a remote node facade
    (RpcServiceServer's process shape). `/metrics` and `/trace` forward to
    the node core's registry/tracer by default (split-mode deployments used
    to serve an empty `/metrics` because nothing bound node metrics here)."""

    def __init__(
        self,
        facade_host: str,
        facade_port: int,
        host: str = "127.0.0.1",
        port: int = 0,
        ssl_context=None,
        metrics=None,
        tracer=None,
        health=None,
    ):
        from ..rpc.http_server import RpcHttpServer

        self.remote = RemoteJsonRpc(facade_host, facade_port)
        self.telemetry = RemoteTelemetry(facade_host, facade_port)
        self.http = RpcHttpServer(
            self.remote, host=host, port=port, ssl_context=ssl_context,
            metrics=metrics if metrics is not None else self.telemetry,
            tracer=tracer if tracer is not None else self.telemetry,
            health=health if health is not None else self.telemetry,
        )
        self.port = self.http.port

    def start(self) -> None:
        self.http.start()

    def stop(self) -> None:
        self.http.stop()
        self.remote.close()
        self.telemetry.close()

"""Circuit breaker + degraded-mode registry.

Reference analogs: the tars heartbeat/reconnect machinery keeps a servant's
liveness state and stops routing to a dead endpoint until it answers again;
TiKVStorage's switch handler flips the scheduler into a recovery term. Here
the same pattern is generic: a :class:`CircuitBreaker` trips after repeated
failures (stops hammering a dead path, half-opens a probe after a cooldown)
and every tripped breaker — plus any subsystem that self-reports — lands in
the process-wide :class:`HealthRegistry`, which `GET /health` and the
``fisco_component_health`` metrics gauge expose.

Degraded mode is a REPORTING state, not a stop: a degraded component keeps
serving through whatever fallback its caller wired (host-path crypto,
surviving executors, re-armed 2PC recovery). The registry exists so an
operator (or tool/check_resilience.py) can see the transition and confirm
the recovery edge.
"""

from __future__ import annotations

import json
import threading
import time


class HealthRegistry:
    """Process-wide component health: ``ok`` / ``degraded`` per component.

    Transitions push a ``fisco_component_health{component=...}`` gauge
    (1 = ok, 0 = degraded) and count into
    ``fisco_component_degraded_total`` so /metrics shows flap history even
    after recovery.
    """

    def __init__(self):
        self._lock = threading.Lock()
        # name -> (status, reason, since_monotonic, critical)
        self._components: dict[str, tuple[str, str, float, bool]] = {}
        self.transitions = 0

    # -- reporting -----------------------------------------------------------

    def ok(self, component: str, reason: str = "") -> None:
        self._set(component, "ok", reason, True)

    def degrade(self, component: str, reason: str = "", critical: bool = True) -> None:
        """``critical=True`` (default): the node cannot serve its core duty
        (e.g. a storage shard is gone — 2PC cannot commit) and /health
        answers 503 so probes take it out of rotation. ``critical=False``:
        the node KEEPS serving through a fallback (survivor executors, the
        host crypto loop) — /health stays
        200 with the degradation in the JSON body, because evicting a
        correctly-serving node over a permanent informational latch would
        turn one slow path into an outage."""
        self._set(component, "degraded", reason, critical)

    def _set(self, component: str, status: str, reason: str, critical: bool) -> None:
        changed = False
        with self._lock:
            prev = self._components.get(component)
            if prev is None or prev[0] != status:
                changed = True
                self.transitions += 1
            self._components[component] = (
                status, reason, time.monotonic(), critical
            )
        if changed:
            self._export(component, status, reason)

    def _export(self, component: str, status: str, reason: str) -> None:
        try:  # lazy: resilience must import without dragging metrics in
            from ..utils.metrics import REGISTRY

            REGISTRY.gauge_set(
                f'fisco_component_health{{component="{component}"}}',
                1.0 if status == "ok" else 0.0,
                help="component health (1 ok, 0 degraded)",
            )
            if status != "ok":
                REGISTRY.counter_add(
                    f'fisco_component_degraded_total{{component="{component}"}}',
                    1.0,
                    help="degraded-mode entries per component",
                )
        except Exception as e:
            from ..utils.log import note_swallowed

            note_swallowed("health.metrics_export", e)
        if status != "ok":
            from ..utils.log import get_logger

            get_logger("health").warning(
                "component %s DEGRADED: %s", component, reason or "unspecified"
            )

    # -- querying ------------------------------------------------------------

    def status(self, component: str) -> str:
        with self._lock:
            ent = self._components.get(component)
        return ent[0] if ent is not None else "unknown"

    def overall(self) -> str:
        """``critical`` (a critical component is degraded: not ready, 503) >
        ``degraded`` (serving through fallbacks, 200 + JSON detail) >
        ``ok``."""
        with self._lock:
            vals = list(self._components.values())
        if any(s != "ok" and c for s, _r, _t, c in vals):
            return "critical"
        if any(s != "ok" for s, _r, _t, _c in vals):
            return "degraded"
        return "ok"

    def snapshot(self) -> dict:
        now = time.monotonic()
        with self._lock:
            comps = {
                name: {
                    "status": s,
                    "reason": r,
                    "for_seconds": round(now - t, 3),
                    "critical": c,
                }
                for name, (s, r, t, c) in sorted(self._components.items())
            }
        return {"status": self.overall(), "components": comps}

    def to_json(self) -> str:
        return json.dumps(self.snapshot())

    def reset(self) -> None:
        """Test isolation: forget all components."""
        with self._lock:
            self._components.clear()
            self.transitions = 0


# the process registry — subsystems report here, GET /health serves it
HEALTH = HealthRegistry()


class CircuitBreaker:
    """Closed -> open after ``failure_threshold`` consecutive failures;
    half-open probe after ``reset_timeout``; closes again on success.

    Wired to a :class:`HealthRegistry` component: tripping reports
    ``degraded``, closing reports ``ok``. Thread-safe; `allow()` grants the
    half-open probe to exactly one caller per cooldown window.
    """

    def __init__(
        self,
        name: str,
        failure_threshold: int = 3,
        reset_timeout: float = 30.0,
        registry: HealthRegistry | None = None,
        critical: bool = True,
    ):
        self.name = name
        self.failure_threshold = max(1, int(failure_threshold))
        self.reset_timeout = float(reset_timeout)
        self.registry = registry if registry is not None else HEALTH
        # critical=False: tripping reports a SERVING degradation (a fallback
        # carries the load) — /health stays 200 (see HealthRegistry.degrade)
        self.critical = critical
        self._lock = threading.Lock()
        self._failures = 0
        self._opened_at: float | None = None
        self._probing = False

    @property
    def state(self) -> str:
        with self._lock:
            if self._opened_at is None:
                return "closed"
            if time.monotonic() - self._opened_at >= self.reset_timeout:
                return "half-open"
            return "open"

    def allow(self) -> bool:
        """May the protected path be attempted right now?"""
        with self._lock:
            if self._opened_at is None:
                return True
            if time.monotonic() - self._opened_at < self.reset_timeout:
                return False
            if self._probing:
                return False  # one probe at a time
            self._probing = True
            return True

    def record_success(self) -> None:
        with self._lock:
            was_open = self._opened_at is not None
            self._failures = 0
            self._opened_at = None
            self._probing = False
        if was_open:
            self.registry.ok(self.name, "recovered")

    def release_probe(self) -> None:
        """Free the half-open probe slot WITHOUT recording a verdict — for
        callers whose protected attempt never reached an outcome (e.g. a
        data error that fails both paths). Without this, an exception
        escaping the probe would leave ``_probing`` latched and wedge the
        breaker in half-open (allow() false forever)."""
        with self._lock:
            self._probing = False

    def record_failure(self, reason: str = "") -> None:
        with self._lock:
            self._failures += 1
            self._probing = False
            tripping = (
                self._opened_at is None
                and self._failures >= self.failure_threshold
            )
            if tripping:
                self._opened_at = time.monotonic()
            elif self._opened_at is not None:
                # failed probe: restart the cooldown window
                self._opened_at = time.monotonic()
        if tripping:
            self.registry.degrade(
                self.name,
                reason or f"{self._failures} consecutive failures",
                critical=self.critical,
            )

    def call(self, fn, *args, fallback=None, classify=(Exception,), **kwargs):
        """Run ``fn`` under the breaker; on open (or on failure) route to
        ``fallback`` when provided, else re-raise."""
        if not self.allow():
            if fallback is not None:
                return fallback(*args, **kwargs)
            raise RuntimeError(f"circuit {self.name} open")
        try:
            out = fn(*args, **kwargs)
        except classify as e:  # type: ignore[misc]
            self.record_failure(f"{type(e).__name__}: {e}")
            if fallback is not None:
                return fallback(*args, **kwargs)
            raise
        except BaseException:
            # unclassified escape: no verdict, but the probe slot must not
            # stay latched
            self.release_probe()
            raise
        self.record_success()
        return out

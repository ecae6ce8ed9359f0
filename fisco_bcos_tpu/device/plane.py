"""DevicePlane — the shared, shape-bucketed batch scheduler for all device
crypto dispatch.

Before this layer, every caller — txpool admission (txpool/txpool.py
submit_batch), proposal verification (consensus/engine.py _verify_and_fill,
consensus/block_validator.py check_block) and tx sync (sync/tx_sync.py
_on_push) — ran its own synchronous device batch, so arbitrary per-caller
batch shapes caused recompile churn (visible in the compile-vs-cached
counters) and the device plane never saturated: the FPGA-ECDSA engine and
EdDSA/BLS committee-consensus studies (PAPERS.md, arxiv 2112.02229 /
2302.00418) both get their wins from ONE saturated verification engine fed
by a request queue, not from per-caller batches.

The plane is that engine's scheduler:

- **Per-op request queue, future-based results.** Callers submit
  (op, payload, item-count, executor) and get a ``concurrent.futures.Future``
  back; the dispatch seam (device/dispatch.py, which every crypto batch
  method enters through) blocks on it, so caller APIs are unchanged.
- **Micro-batch coalescer.** A single worker drains each op's queue after a
  bounded window (``FISCO_DEVICE_WINDOW_MS``, default 2 ms) or when the
  queued item count crosses the high-water mark
  (``FISCO_DEVICE_HIGH_WATER``, default 4096) — concurrent
  admission/consensus/sync requests merge into one device program.
- **Shape bucketing.** Merged batches dispatch through the existing
  bucket-padded host wrappers (ops/hash_common._bucket ladder), so the jit
  cache converges to ladder-many compiled programs instead of one per batch
  size; ``fisco_device_compile_total`` stays ≤ the ladder size
  (tool/check_device_plane.py asserts it).
- **Priority lanes.** consensus > admission > sync > proof among
  dispatch-ready op groups, with starvation-free draining: any group whose
  oldest request
  has waited past ``FISCO_DEVICE_STARVATION_MS`` (default 50 ms) preempts
  lane order, oldest first — a gossip flood cannot park a QC check, and a
  stream of QC checks cannot park gossip forever.
- **Group-fair selection (multi-tenant isolation).** Every request carries
  the chain group that produced it (``device_group``, tagged by each
  group's txpool). When a dispatch-ready op queue holds traffic from MORE
  than one tenant group, the dispatch is assembled by deficit-weighted
  round-robin across groups *within* each priority lane: each group earns
  ``FISCO_DEVICE_GROUP_QUANTUM`` items (x its
  ``FISCO_DEVICE_GROUP_WEIGHTS`` weight) per round and spends its deficit
  on its oldest requests, and the merged batch is capped at the high-water
  mark — so one group flooding admission batches cannot fill every device
  program while another group's batch sits queued behind the backlog.
  Deferred requests keep their enqueue time (aging still applies) and
  count into ``fisco_device_plane_deferred_total{op,group}``. Single-group
  queues take the exact pre-fairness path: everything merges, no cap.

Executors run ON the worker thread with a thread-local marker set
(``in_plane_executor()``); the dispatch seam reads it, so an executor
calling back into a seam (e.g. ed25519 batch_recover → batch_verify) runs
that body inline instead of deadlocking the single worker against itself.
Invalid rows lower validity-lane bits — they never raise.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import Future
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable

# dispatch priority per lane, lower = sooner (consensus is on the critical
# path of block time; admission feeds the next proposal; sync is gossip;
# proof is the read path — light-client proof storms must never starve the
# write path, so their tree builds rank below everything, bounded only by
# the starvation aging like every other lane)
LANES = {"consensus": 0, "admission": 1, "sync": 2, "proof": 3}
DEFAULT_LANE = "admission"

_tls = threading.local()


def in_plane_executor() -> bool:
    """True on the plane worker while it runs an executor: a seam entered
    from there must run inline, never queue (device/dispatch.enqueue)."""
    return bool(getattr(_tls, "in_exec", False))


def current_lane() -> str:
    return getattr(_tls, "lane", DEFAULT_LANE)


def current_group() -> str:
    """The tenant (chain group) this thread's device batches belong to;
    "" = ungrouped (single-group deployments, internal callers)."""
    return getattr(_tls, "group", "")


@contextmanager
def device_group(name: str):
    """Tag device-crypto calls in this thread with their tenant group, the
    unit the plane's deficit-round-robin arbitrates between. Same contract
    as :func:`device_lane`: the txpool wraps its batch calls, everything
    submitted underneath inherits the tag."""
    prev = getattr(_tls, "group", "")
    _tls.group = name
    try:
        yield
    finally:
        _tls.group = prev


@contextmanager
def device_lane(name: str):
    """Tag device-crypto calls in this thread with a priority lane.

    Callers keep their APIs (the issue's seam contract): the consensus
    engine / block validator / tx sync wrap their verification calls in
    ``with device_lane("consensus"/"sync")`` and every batch submitted
    underneath inherits the lane; untagged callers default to "admission".
    """
    prev = getattr(_tls, "lane", DEFAULT_LANE)
    _tls.lane = name
    try:
        yield
    finally:
        _tls.lane = prev


@dataclass
class PlaneRequest:
    """One queued batch: op key, op-specific payload, item count, lane.
    ``ctx`` is the submitting caller's trace context — the merged dispatch
    span links back to it, and the caller's trace gets a derived
    ``device.plane.queue`` record (submit to dispatch) carrying the batch's
    span id; the caller's own blocked time is ``device.plane.wait``."""

    op: str
    payload: object
    n: int
    lane: str
    t_enq: float
    future: Future
    ctx: object = None
    group: str = ""  # tenant group (deficit-round-robin arbitration unit)


# wait-time buckets: the window is ~2 ms, starvation trips at ~50 ms, and
# anything past a few hundred ms means the plane is the bottleneck
WAIT_BUCKETS_MS = (1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 1000.0)
OCCUPANCY_BUCKETS = (0.25, 0.5, 0.75, 0.9, 1.0)


class DevicePlane:
    """The coalescing scheduler. One process-wide instance (``get_plane``)
    serves every crypto seam; standalone instances exist only in tests.

    Executors are bound lazily at submit time (first one registered per op
    wins) — the plane imports nothing from the crypto layer above it, so
    there is no import cycle and no registration ordering to get wrong.
    An executor receives the request list and returns one result per
    request, in order; it runs with the in-executor marker set.
    """

    def __init__(
        self,
        window_ms: float | None = None,
        high_water: int | None = None,
        starvation_ms: float | None = None,
        autostart: bool = True,
    ):
        from ..utils import env_float as _env

        if window_ms is not None:
            self.window_ms = float(window_ms)
        elif os.environ.get("FISCO_DEVICE_WINDOW_MS"):
            self.window_ms = _env("FISCO_DEVICE_WINDOW_MS", 2.0)
        else:
            self.window_ms = self._default_window_ms()
        self.high_water = (
            int(_env("FISCO_DEVICE_HIGH_WATER", 4096.0))
            if high_water is None
            else int(high_water)
        )
        self.starvation_ms = (
            _env("FISCO_DEVICE_STARVATION_MS", 50.0)
            if starvation_ms is None
            else float(starvation_ms)
        )
        # group-fair selection: items each tenant group earns per DRR round,
        # scaled by its weight (FISCO_DEVICE_GROUP_WEIGHTS="g0=2,g1=1");
        # deficits persist across dispatches while a group has backlog and
        # reset when it drains (classic DRR)
        self.group_quantum = max(1, int(_env("FISCO_DEVICE_GROUP_QUANTUM", 256.0)))
        self.group_weights: dict[str, float] = {}
        for part in os.environ.get("FISCO_DEVICE_GROUP_WEIGHTS", "").split(","):
            name, _, w = part.strip().partition("=")
            if name and w:
                try:
                    self.group_weights[name] = max(float(w), 1e-6)
                except ValueError:
                    pass
        self._deficit: dict[str, float] = {}
        self._drr_rotor = 0  # rotates the serving order across dispatches
        self._autostart = autostart
        # Condition over an EXPLICIT package-created RLock: a bare
        # Condition() allocates its lock inside threading.py, which the
        # lock-order factory filter skips — this way the plane's guard
        # participates in runtime lock-order recording and the raceguard
        # lockset, like every other package lock
        self._cv = threading.Condition(threading.RLock())
        self._pending: dict[str, list[PlaneRequest]] = {}
        self._exec_fns: dict[str, Callable] = {}
        self._thread: threading.Thread | None = None
        self._busy = False
        # stats (mutated under _cv; snapshot via stats())
        self.requests = 0
        self.dispatches = 0
        self.merged_requests = 0  # requests that shared a dispatch with others
        self.items = 0
        self._wait_ms: deque[float] = deque(maxlen=4096)

    @staticmethod
    def _default_window_ms() -> float:
        """2 ms on accelerator backends (every merged straggler is a device
        dispatch saved; the value is inherited, not measured on a local
        chip — ROADMAP Queue 3); 0 on CPU-XLA backends, where dispatches
        are sub-ms native host loops and an idle-queue wait would tax every
        sequential batch call for nothing — bursts still coalesce while the
        worker is busy. A backend that fails to initialise raises here, at
        plane construction, instead of reading as an accelerator."""
        from ..utils.jaxenv import device_backend_is_cpu

        return 0.0 if device_backend_is_cpu() else 2.0

    # -- submission ----------------------------------------------------------

    def submit(self, op: str, payload, n: int, exec_fn: Callable) -> Future:
        """Queue one batch for op; returns a Future of the executor's
        per-request result. The caller's current lane — and trace context —
        are captured here."""
        from ..observability.tracer import TRACER

        req = PlaneRequest(
            op, payload, int(n), current_lane(), time.perf_counter(), Future(),
            ctx=TRACER.current_context() if TRACER.enabled else None,
            group=current_group(),
        )
        with self._cv:
            self._exec_fns.setdefault(op, exec_fn)
            self._pending.setdefault(op, []).append(req)
            self.requests += 1
            self.items += req.n
            if self._autostart:
                self._ensure_thread_locked()
            self._cv.notify_all()
        from ..utils.metrics import REGISTRY

        REGISTRY.counter_add(
            f'fisco_device_plane_requests_total{{op="{op}",lane="{req.lane}"}}',
            1.0,
            help="batches submitted to the device plane by op and lane",
        )
        return req.future

    # -- scheduler -----------------------------------------------------------

    def _ensure_thread_locked(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, name="device-plane", daemon=True
            )
            self._thread.start()

    def _group_ready(self, reqs: list[PlaneRequest], now: float) -> bool:
        age_ms = (now - reqs[0].t_enq) * 1e3
        return age_ms >= self.window_ms or sum(r.n for r in reqs) >= self.high_water

    def _pick_ready_locked(self, now: float):
        """Pop the dispatch-ready op group with the best claim, or None.

        Ready = window elapsed since the group's oldest request, or item
        count at/over high water. Among ready groups: starved groups (oldest
        request past starvation_ms) first, oldest first — the aging bound
        that makes draining starvation-free; then by best lane priority
        present in the group; ties to the oldest group.

        Returns ``(op, taken, deferred)``: multi-tenant queues are trimmed
        by :meth:`_select_fair`; requests it defers go back to the FRONT of
        the op's queue (enqueue times intact, so aging and window readiness
        survive) and are reported for the deferred counter."""
        best_op = None
        best_key = None
        for op, reqs in self._pending.items():
            if not reqs or not self._group_ready(reqs, now):
                continue
            age_ms = (now - reqs[0].t_enq) * 1e3
            if age_ms >= self.starvation_ms:
                key = (0, -age_ms, reqs[0].t_enq)
            else:
                lane_rank = min(LANES.get(r.lane, 1) for r in reqs)
                key = (1, lane_rank, reqs[0].t_enq)
            if best_key is None or key < best_key:
                best_key, best_op = key, op
        if best_op is None:
            return None
        taken, deferred = self._select_fair(self._pending.pop(best_op))
        if deferred:
            self._pending[best_op] = deferred
        return best_op, taken, deferred

    def _weight(self, group: str) -> float:
        return self.group_weights.get(group, 1.0)

    def _select_fair(self, reqs: list[PlaneRequest]):
        """Deficit-weighted round-robin across tenant groups within each
        priority lane: assemble one merged dispatch of at most
        ``high_water`` items (a single oversized request still dispatches
        whole — requests are indivisible), leaving the surplus queued.

        Single-tenant queues (the common case, and every pre-multi-group
        deployment) take the exact legacy path: all requests merge, no cap.
        Returns ``(taken, deferred)`` with FIFO order preserved inside each
        (lane, group); ``taken`` is never empty."""
        all_groups = {r.group for r in reqs}
        if len(all_groups) <= 1:
            return reqs, []
        cap = self.high_water
        # per-round quantum scaled so one round across n groups roughly
        # fills the cap — an unscaled quantum >= cap would let whichever
        # group serves first spend the whole dispatch before the others'
        # turns, which is exactly the monopoly DRR exists to prevent
        base_q = max(1, min(self.group_quantum, cap // len(all_groups)))
        by_lane: dict[int, dict[str, deque]] = {}
        for r in reqs:
            lane_q = by_lane.setdefault(LANES.get(r.lane, 1), {})
            lane_q.setdefault(r.group, deque()).append(r)
        taken: list[PlaneRequest] = []
        taken_ids: set[int] = set()
        total = 0
        rotor = self._drr_rotor
        self._drr_rotor += 1
        for rank in sorted(by_lane):
            queues = by_lane[rank]
            # rotate the serving order across dispatches so no group is
            # structurally first every time
            order = list(queues)
            start = rotor % len(order)
            order = order[start:] + order[:start]
            while total < cap and any(queues.values()):
                # one DRR round: every backlogged group earns one quantum,
                # then spends its deficit on its oldest requests — a huge
                # request accumulates rounds until funded, so nothing
                # starves, it just waits its proportional turn
                for g in order:
                    q = queues[g]
                    if not q:
                        continue
                    self._deficit[g] = (
                        self._deficit.get(g, 0.0) + base_q * self._weight(g)
                    )
                    while q and total < cap and self._deficit[g] >= q[0].n:
                        r = q.popleft()
                        self._deficit[g] -= r.n
                        taken.append(r)
                        taken_ids.add(id(r))
                        total += r.n
                    if total >= cap:
                        break
            if total >= cap:
                break
        deferred = [r for r in reqs if id(r) not in taken_ids]
        # classic DRR: a group that drained its backlog forfeits its credit
        # (deficits only persist across dispatches while traffic is queued)
        still_backlogged = {r.group for r in deferred}
        for g in {r.group for r in reqs} - still_backlogged:
            self._deficit.pop(g, None)
        return taken, deferred

    def _note_deferred(self, op: str, deferred: list[PlaneRequest]) -> None:
        """Export fairness decisions (called OUTSIDE the scheduler lock)."""
        from ..utils.metrics import REGISTRY

        if not deferred or not REGISTRY.enabled:
            return
        per_group: dict[str, int] = {}
        for r in deferred:
            per_group[r.group] = per_group.get(r.group, 0) + 1
        for g, n in per_group.items():
            REGISTRY.counter_add(
                f'fisco_device_plane_deferred_total{{group="{g}",op="{op}"}}',
                float(n),
                help="requests deferred to a later dispatch by group-fair "
                "deficit-round-robin (the multi-tenant backpressure signal)",
            )

    def _next_timeout_s(self, now: float) -> float | None:
        """Seconds until the earliest group becomes window-ready; None when
        the queue is empty (sleep until notified)."""
        deadlines = [
            reqs[0].t_enq + self.window_ms / 1e3
            for reqs in self._pending.values()
            if reqs
        ]
        if not deadlines:
            return None
        return max(min(deadlines) - now, 0.0)

    def _run(self) -> None:
        while True:
            with self._cv:
                picked = None
                while picked is None:
                    picked = self._pick_ready_locked(time.perf_counter())
                    if picked is None:
                        self._cv.wait(self._next_timeout_s(time.perf_counter()))
                op, reqs, deferred = picked
                self._busy = True
            try:
                from ..observability.pipeline import PIPELINE

                self._note_deferred(op, deferred)
                with PIPELINE.busy("device_plane"):
                    self._dispatch(op, reqs)
            finally:
                with self._cv:
                    self._busy = False
                    self._cv.notify_all()

    def _dispatch(self, op: str, reqs: list[PlaneRequest]) -> None:
        # Once a group is popped from _pending, its futures exist only here:
        # EVERYTHING (telemetry included) runs under the catch-all so that no
        # failure mode can drop them unresolved — a lost future wedges a
        # caller blocked in .result() forever.
        try:
            from ..observability.tracer import TRACER

            # the merged-batch span: parented to the first absorbed caller,
            # LINKED to every caller it coalesced — the Perfetto view of N
            # transactions converging into one device program. Entering it
            # on this worker thread also hands the trace context to the
            # executor, so the device.<op> spans inside nest under it.
            # SAMPLED callers only: an unsampled first caller would noop
            # the whole batch span (suppressing every sampled caller's wait
            # record), and links to unsampled ctxs would dangle.
            ctxs = [
                r.ctx for r in reqs if r.ctx is not None and r.ctx.sampled
            ]
            span = TRACER.span(
                "device.plane.dispatch",
                parent=ctxs[0] if ctxs else None,
                links=ctxs,
                op=op,
                requests=len(reqs),
                items=sum(r.n for r in reqs),
            )
            with span:
                self._record_dispatch(op, reqs, getattr(span, "ctx", None))
                _tls.in_exec = True
                try:
                    results = self._exec_fns[op](reqs)
                finally:
                    _tls.in_exec = False
            if len(results) != len(reqs):
                raise RuntimeError(
                    f"plane executor for {op} returned {len(results)} results"
                    f" for {len(reqs)} requests"
                )
            for r, res in zip(reqs, results):
                r.future.set_result(res)
        except BaseException as e:  # noqa: BLE001 — futures must never wedge
            for r in reqs:
                if not r.future.done():
                    r.future.set_exception(e)

    def _record_dispatch(
        self, op: str, reqs: list[PlaneRequest], batch_ctx=None
    ) -> None:
        from ..observability.tracer import TRACER
        from ..utils.metrics import REGISTRY

        now = time.perf_counter()
        total = sum(r.n for r in reqs)
        with self._cv:
            self.dispatches += 1
            if len(reqs) > 1:
                self.merged_requests += len(reqs)
            for r in reqs:
                self._wait_ms.append((now - r.t_enq) * 1e3)
        if batch_ctx is not None:
            # close the loop from the caller side: each absorbed caller's
            # trace gets its queue wait as a span naming the merged batch's
            # span id (the fan-in edge, readable from either end)
            for r in reqs:
                if r.ctx is not None and r.ctx.sampled:
                    TRACER.record(
                        "device.plane.queue",
                        t0=r.t_enq,
                        dur=now - r.t_enq,
                        parent_ctx=r.ctx,
                        derived=True,  # submit -> dispatch, seen from here
                        op=op,
                        lane=r.lane,
                        batch_span=f"{batch_ctx.span_id:016x}",
                    )
        from ..observability.device import (
            LEDGER,
            device_obs_enabled,
            observe_phase,
        )

        # ledger attribution rides FISCO_DEVICE_OBS alone — it must keep
        # working with the metrics registry off (the telemetry A/B leg),
        # so it runs BEFORE the registry early-return. The queue segment
        # is labeled with the plane's dispatch op; the kernel spans inside
        # the executor carry compile/transfer/execute under their program
        # op names (ISSUE 13 phase decomposition).
        obs = device_obs_enabled()
        if obs:
            t_obs = time.perf_counter()
            LEDGER.note_phases(
                op, {"queue": sum((now - r.t_enq) * 1e3 for r in reqs)}
            )
            # fusion-frontier evidence (ISSUE 20): count the (prev, op)
            # dispatch edge — what --fusion-report joins with the static
            # per-program boundary costs
            LEDGER.note_adjacency(op)
            LEDGER.add_overhead(time.perf_counter() - t_obs)
        if not REGISTRY.enabled:
            return
        for r in reqs:
            wait_ms = (now - r.t_enq) * 1e3
            REGISTRY.observe(
                "fisco_device_plane_wait_ms",
                wait_ms,
                buckets=WAIT_BUCKETS_MS,
                help="queue wait from submit to dispatch, per lane",
                lane=r.lane,
            )
            if obs:
                observe_phase(op, "queue", wait_ms)
        REGISTRY.counter_add(
            f'fisco_device_plane_dispatch_total{{op="{op}"}}',
            1.0,
            help="merged device dispatches by op (requests/dispatches = "
            "coalesce ratio)",
        )
        if len(reqs) > 1:
            REGISTRY.counter_add(
                f'fisco_device_plane_coalesced_total{{op="{op}"}}',
                float(len(reqs)),
                help="requests that shared a merged dispatch with others",
            )
        from ..observability import BATCH_BUCKETS
        from ..ops.hash_common import bucket_batch

        REGISTRY.observe(
            "fisco_device_plane_batch_items",
            total,
            buckets=BATCH_BUCKETS,
            help="merged batch sizes dispatched by the plane",
            op=op,
        )
        bucket = bucket_batch(max(total, 1))
        REGISTRY.observe(
            "fisco_device_plane_bucket_occupancy",
            total / bucket if bucket else 0.0,
            buckets=OCCUPANCY_BUCKETS,
            help="real rows / bucket-padded rows per dispatch (batch dim"
            " only; pad waste = 1 - occupancy)",
            op=op,
        )

    # -- introspection -------------------------------------------------------

    def _depth(self) -> int:
        with self._cv:
            return sum(sum(r.n for r in reqs) for reqs in self._pending.values())

    def lane_depths(self) -> dict[str, int]:
        """Queued items by priority lane — the pipeline observatory's
        per-lane backpressure watermark (one probe, one lock round)."""
        with self._cv:
            out: dict[str, int] = {}
            for reqs in self._pending.values():
                for r in reqs:
                    out[r.lane] = out.get(r.lane, 0) + r.n
        for lane in LANES:
            out.setdefault(lane, 0)
        return out

    def coalesce_ratio(self) -> float:
        """Requests per device dispatch (≥ 1.0; 1.0 = no coalescing won)."""
        with self._cv:
            return self.requests / self.dispatches if self.dispatches else 1.0

    def wait_p99_ms(self) -> float:
        with self._cv:
            waits = sorted(self._wait_ms)
        if not waits:
            return 0.0
        return waits[min(len(waits) - 1, int(0.99 * len(waits)))]

    def stats(self) -> dict:
        with self._cv:
            return {
                "requests": self.requests,
                "dispatches": self.dispatches,
                "merged_requests": self.merged_requests,
                "items": self.items,
                "queue_depth": sum(
                    sum(r.n for r in reqs) for reqs in self._pending.values()
                ),
            }

    def drain(self, timeout: float = 60.0) -> bool:
        """Block until the queue is empty and no dispatch is in flight
        (bench/smoke hook); False on timeout."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while any(self._pending.values()) or self._busy:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cv.wait(min(remaining, 0.05))
        return True

    def _register_gauges(self) -> None:
        """Register the queue-depth gauge. Called for the process singleton
        only (get_plane) — the registry holds a strong ref to the closure
        and last-registration-wins, so a throwaway instance registering
        would hijack the metric and pin itself in memory."""
        try:
            from ..utils.metrics import REGISTRY

            REGISTRY.gauge_fn(
                "fisco_device_plane_queue_depth",
                lambda: float(self._depth()),
                help="items currently queued in the device plane",
            )
        except Exception as e:  # metrics layer disabled/unavailable — plane works
            from ..utils.log import note_swallowed

            note_swallowed("device.plane.gauge_register", e)


def plane_wait(fut: Future):
    """Block on a plane future, attributing the wait to the calling
    thread's ambient pipeline stage (``<stage> blocked_on=device_plane`` —
    the edge that says the admission/consensus/execute worker was parked
    behind the shared crypto engine, not doing its own work). Every batch
    queued into the plane resolves its future through here.
    The ``device.plane.wait`` span is the caller's side of it, start to end
    on the caller's thread: queue, coalescing window, dispatch and result."""
    from ..observability.pipeline import PIPELINE
    from ..observability.tracer import TRACER

    with TRACER.span("device.plane.wait"), PIPELINE.blocked("device_plane"):
        return fut.result()


def plane_wait_deferred(fut: Future):
    """:func:`plane_wait` for two-phase hash futures whose resolved value
    is a deferred-sync callable: BOTH the queue wait and the device sync
    are the caller blocked behind the plane, so both run inside the one
    blocked attribution — otherwise the sync (the expensive half: it waits
    for the device) would count as the caller's busy time."""
    from ..observability.pipeline import PIPELINE
    from ..observability.tracer import TRACER

    with TRACER.span("device.plane.wait"), PIPELINE.blocked("device_plane"):
        return fut.result()()


_PLANE: DevicePlane | None = None
_PLANE_LOCK = threading.Lock()


def get_plane() -> DevicePlane:
    """The process-wide plane every crypto seam shares (coalescing across
    callers is the whole point — per-caller planes would recreate the
    per-caller batch problem)."""
    global _PLANE
    if _PLANE is None:
        with _PLANE_LOCK:
            if _PLANE is None:
                _PLANE = DevicePlane()
                _PLANE._register_gauges()
    return _PLANE

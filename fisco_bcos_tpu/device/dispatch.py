"""The dispatch seam — where a crypto batch runs is decided here and nowhere
else.

The crypto classes (crypto/suite.py, crypto/bls.py, crypto/admission.py)
normalise their arguments and describe an operation as a :class:`BatchOp`:
what it is called in the metrics, which plane queue merges it, and its legs
(``native`` host loop, ``device`` program, ``host`` fallback). This module
owns the rest:

- **the entry** (:func:`enqueue`, :func:`dispatch`): a batch is queued into
  the shared :class:`~.plane.DevicePlane` and the caller waits — unless the
  batch is empty, or the caller already *is* the plane worker (an executor
  that re-enters a seam, as ed25519 ``batch_recover`` → ``batch_verify``
  does, would wait on the one worker it is running on), in which case the
  same body runs inline;
- **the policy** (:func:`use_native_batch`): native host loop or device
  program, asked once per dispatch with the MERGED size;
- **the legs** (:func:`run_legs`): native (an answer of ``None`` — library
  missing — falls through) → device under the breaker with the host loop as
  fallback where the op has one → the leg taken noted in
  ``fisco_device_dispatch_path_total{op,path}``;
- **merge and slice** (:func:`_merge_exec`): the one plane executor of every
  BatchOp.
"""

from __future__ import annotations

import os
import threading
from collections.abc import Callable
from concurrent.futures import Future
from dataclasses import dataclass
from itertools import chain

import numpy as np

from ..observability.device import note_dispatch_path as _note_dispatch_path
from ..utils.jaxenv import device_backend_is_cpu
from .plane import get_plane, in_plane_executor, plane_wait


@dataclass(frozen=True)
class BatchOp:
    """What one batch operation is. Every leg takes the request's fields
    (arrays or lists, batch-leading) and returns an array or a tuple of
    arrays with one row per item. The plane binds one executor per plane op,
    process-wide (the first one wins), so legs must not depend on the state
    of the object that built the description."""

    label: str  # fisco_device_dispatch_path_total{op=...}, failures ledger
    # the plane merges requests of one op; None: never queued (one verdict
    # for the whole call, nothing to cut per request — run_legs only)
    plane_op: str | None
    device: Callable  # the device program
    native: Callable | None = None  # native host loop; answers None without its library
    host: Callable | None = None  # the breaker's fallback, bit-identical, slow


# -- the policy ---------------------------------------------------------------

# Batches below this ride the native host loop instead of the device: a
# device program pays a fixed dispatch + transfer + sync cost regardless of
# batch size, while the native single-item path is ~0.3ms/sig, so there is
# a break-even batch.  PBFT QC signature lists (3-4 sigs per block,
# BlockValidator.cpp:141-177) and small-block admission are the
# beneficiaries.  The value is inherited, NOT measured on a local chip —
# deriving it from the device observatory is ROADMAP Queue 3.  Results are
# bit-identical across both legs (tests/test_native_ec.py pins it).
_SMALL_BATCH = 256


def device_min_batch() -> int:
    """Host-vs-device cutover: batches below this ride the native host loop.

    ``FISCO_DEVICE_MIN_BATCH`` overrides the hardcoded default — the right
    cutover depends on the device's fixed per-dispatch cost, which is not
    measured on a local chip yet. Read per call (an env read, ~100ns
    against a batch dispatch) so operators and tests can retune without a
    restart."""
    raw = os.environ.get("FISCO_DEVICE_MIN_BATCH")
    if raw:
        try:
            return max(0, int(raw))
        except ValueError:
            pass
    return _SMALL_BATCH


def use_native_batch(n: int, label: str = "") -> bool:
    """Whether an n-item batch of op ``label`` should ride the native host
    loop instead of a device program: below :func:`device_min_batch`, or at
    any size on a CPU-XLA backend (there the native C loop beats the XLA
    program everywhere: ~0.3ms/sig against 4-16ms/sig of emulated 256-bit
    limb arithmetic). ``FISCO_FORCE_DEVICE_ADMISSION=1`` pins the admission
    ops to the device program (tests and ``chip_smoke.py --rehearse`` use it
    to cover the device path on CPU hosts)."""
    if label == "admission" and os.environ.get("FISCO_FORCE_DEVICE_ADMISSION"):
        return False
    return 0 < n and (n < device_min_batch() or device_backend_is_cpu())


# -- device-path circuit breaker (resilience/) -------------------------------

_DEVICE_BREAKER = None
_DEVICE_BREAKER_LOCK = threading.Lock()


def device_breaker():
    """Breaker over the compiled device batch plane. It can fail in the
    field — a lost accelerator, device OOM on an oversized trace, a
    driver hiccup — and consensus must keep verifying: each failure falls
    back to the host loop for THAT batch, and repeated failures trip the
    breaker so admission stops paying a doomed device dispatch before every
    fallback. /health reports `device-crypto` degraded while tripped; a
    half-open probe re-closes it when the device plane answers again."""
    global _DEVICE_BREAKER
    if _DEVICE_BREAKER is None:
        from ..resilience import CircuitBreaker

        # double-checked: two racing callers must end up sharing ONE breaker
        # — split breakers would each see half the failures and never trip
        with _DEVICE_BREAKER_LOCK:
            if _DEVICE_BREAKER is None:
                _DEVICE_BREAKER = CircuitBreaker(
                    "device-crypto", failure_threshold=2, reset_timeout=60.0,
                    critical=False,  # host loop keeps serving: slower, not down
                )
    return _DEVICE_BREAKER


def _device_or_host(op: str, device_fn, host_fn, *args):
    """Run the compiled device path for ``op`` under the breaker, degrading
    to the bit-identical host loop. The failure only counts against the
    breaker when the host retry of the SAME args succeeds — a data error
    (bad shape/dtype) re-raises from the host path without tripping
    anything, so one malformed batch cannot demote a healthy device plane.

    Nothing here is silent: the leg taken lands in
    ``fisco_device_dispatch_path_total{op,path}`` (``device``, or
    ``host_fallback`` while the breaker is open), and every device-program
    failure the host loop covered for is counted and kept with its error in
    the device observatory (``GET /device`` → ``failures``)."""
    breaker = device_breaker()
    if not breaker.allow():
        _note_dispatch_path(op, "host_fallback")
        return host_fn(*args)
    _note_dispatch_path(op, "device")
    try:
        out = device_fn(*args)
    except Exception as e:
        try:
            out = host_fn(*args)
        except BaseException:
            # both paths failed: a data error, not a device verdict — free
            # the half-open probe slot or the breaker wedges
            breaker.release_probe()
            raise
        from ..observability.device import LEDGER

        LEDGER.note_failure(op, e)
        breaker.record_failure(f"{type(e).__name__}: {str(e)[:200]}")
        return out
    breaker.record_success()
    return out


# -- the legs -----------------------------------------------------------------


def run_legs(op: BatchOp, n: int, *fields):
    """The body of ``op`` over one (merged) batch of ``n`` items: the plane
    worker runs it for a queued dispatch, and a caller that must not queue
    runs the same thing inline."""
    if op.native is not None and use_native_batch(n, op.label):
        out = op.native(*fields)
        if out is not None:
            _note_dispatch_path(op.label, "native")
            return out
    if op.host is None:
        _note_dispatch_path(op.label, "device")
        return op.device(*fields)
    return _device_or_host(op.label, op.device, op.host, *fields)


# -- the entry ----------------------------------------------------------------


def enqueue(plane_op: str, payload, n: int, exec_fn: Callable) -> Future | None:
    """Queue one batch into the shared plane; None when the caller must run
    the body inline instead: an empty batch, or a call made on the plane
    worker itself (re-entering the queue would deadlock the single worker
    against itself)."""
    if n <= 0 or in_plane_executor():
        return None
    return get_plane().submit(plane_op, payload, n, exec_fn)


def dispatch(op: BatchOp, fields: tuple, n: int):
    """Run one batch of ``op``: merged with whatever else is queued for the
    same plane op, one policy decision and one program for the merged batch,
    this caller's rows cut back out."""
    fut = enqueue(op.plane_op, fields, n, _merge_exec(op))
    if fut is None:
        return run_legs(op, n, *fields)
    return plane_wait(fut)


def _merge_exec(op: BatchOp):
    """The plane executor of a BatchOp: concatenate the requests' fields
    (arrays along the batch axis, lists end to end), run the legs once over
    the merged batch, cut every output by ``r.n``."""

    def run(reqs):
        fields = reqs[0].payload
        if len(reqs) > 1:
            fields = [
                np.concatenate(col, axis=0)
                if isinstance(col[0], np.ndarray)
                else list(chain.from_iterable(col))
                for col in zip(*(r.payload for r in reqs))
            ]
        out = run_legs(op, sum(r.n for r in reqs), *fields)
        many = isinstance(out, tuple)
        cols = [np.asarray(o) for o in (out if many else (out,))]
        results, lo = [], 0
        for r in reqs:
            cut = tuple(c[lo : lo + r.n] for c in cols)
            results.append(cut if many else cut[0])
            lo += r.n
        return results

    return run

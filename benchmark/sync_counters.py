"""Block sync's counters, read for the window: how a caught-up block's time
divides among download, re-verification and execution.

The driver (``drivers/catchup.py``) takes ``snapshot()`` at each edge of the
window, as the harness does with ``counters.py``'s; the readers under
``layers/sync_*.py`` work on the difference, per block the replica applied:

- ``fisco_sync_stage_seconds_total{stage}``: ``decode`` (a response's blocks
  into the download queue), ``qc`` (a gather's headers' signatures, one
  batch), ``verify`` (a gather's transactions through one admission call),
  ``serve_request`` (the serving peer reading and encoding a range);
- the sums of ``fisco_block_execute_latency_ms`` and
  ``fisco_block_commit_latency_ms``: in the window only the replica that
  catches up executes and commits;
- the sum of ``fisco_device_phase_ms{op="admission*"}`` over its phases (the
  plane's queue, marshal, enqueue, sync, unpack): the time the admission
  calls took on the plane, which the caller spends blocked inside ``verify``;
- ``fisco_sync_verify_lanes_total``, ``fisco_sync_verify_calls_total``,
  ``fisco_sync_blocks_applied_total``, ``fisco_sync_blocks_refused_total``.

A program that has none of them gives zeros, and a reader None."""

from __future__ import annotations

STAGES = ("decode", "qc", "verify", "serve_request")


def snapshot() -> dict:
    from fisco_bcos_tpu.observability.device import DEVICE_PHASE_BUCKETS_MS
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    def total(name: str) -> float:
        return sum(REGISTRY.counters_matching(name).values())

    def summed(name: str, op: str = "", **kw) -> float:
        """Sum of the histogram's series (those whose ``op`` label holds ``op``)."""
        return sum(
            s for labels, (_cum, s, _n) in REGISTRY.histogram(name, **kw).snapshot().items()
            if op in dict(labels).get("op", "")
        )

    out = {
        "lanes": total("fisco_sync_verify_lanes_total"),
        "calls": total("fisco_sync_verify_calls_total"),
        "applied": total("fisco_sync_blocks_applied_total"),
        "refused": total("fisco_sync_blocks_refused_total"),
        "execute_ms": summed("fisco_block_execute_latency_ms"),
        "commit_ms": summed("fisco_block_commit_latency_ms"),
        "verify_wait_ms": summed(
            "fisco_device_phase_ms", "admission", buckets=DEVICE_PHASE_BUCKETS_MS),
    }
    for stage in STAGES:
        out[stage + "_ms"] = 1e3 * total(
            f'fisco_sync_stage_seconds_total{{stage="{stage}"}}')
    return out


def per_block(ctx, key: str):
    """Milliseconds of ``key`` per block applied in the window, or None where
    the driver took no snapshots, no block was applied or nothing was counted."""
    before, after = getattr(ctx.cell, "sync0", None), getattr(ctx.cell, "sync1", None)
    if not before or not after:
        return None
    blocks = after["applied"] - before["applied"]
    spent = after[key] - before[key]
    return spent / blocks if blocks > 0 and spent > 0 else None

"""Snapshots of the program's sound counters (ISSUE 23's verdict list): the
admission dispatch-path counter, the compile ledger's episodes and the plane's
queue-wait histogram. The harness takes one at each edge of the window and the
layer readers work on the difference."""

from __future__ import annotations

_PATH = 'fisco_device_dispatch_path_total{op="admission",path="'
_ITEMS = 'fisco_device_items_total{op="admission'


def snapshot() -> dict:
    from fisco_bcos_tpu.device.plane import WAIT_BUCKETS_MS
    from fisco_bcos_tpu.observability.device import LEDGER
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    paths = {
        name[len(_PATH):].split('"')[0]: v
        for name, v in REGISTRY.counters_matching(_PATH).items()
    }
    episodes = {
        f"{r['op']}{r['shape']}": r["cold_compiles"] + r["cache_hits"]
        for r in LEDGER.snapshot()
    }
    waits = REGISTRY.histogram(
        "fisco_device_plane_wait_ms", buckets=WAIT_BUCKETS_MS
    ).snapshot()
    from fisco_bcos_tpu.device.plane import get_plane

    plane = get_plane().stats()
    return {
        # lanes the device admission programs were given (admission, admission_sharded)
        "admission_items": sum(
            v for name, v in REGISTRY.counters_matching(_ITEMS).items()
            if 'op="admission_native"' not in name
        ),
        "plane": {k: plane[k] for k in ("requests", "dispatches", "items")},
        "admission_paths": paths,
        "compile_episodes": episodes,
        "plane_wait_sum_ms": sum(s for _cum, s, _n in waits.values()),
        "plane_wait_count": sum(n for _cum, _s, n in waits.values()),
    }


def delta(before, after):
    """after - before; for a dict entry by entry, zeros left out."""
    if isinstance(after, dict):
        out = {k: delta(before.get(k, 0), v) for k, v in after.items()}
        return {k: v for k, v in out.items() if v}
    return after - before

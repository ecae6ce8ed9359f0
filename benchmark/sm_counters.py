"""The SM leg's counters, read for the window: the admission seam's calls by
leg, the fused SM program's lanes and phases, the SM3 programs by use (the
hash plane's batches, the merkle levels), and which way a merkle tree went.

The driver (``drivers/air4_sm.py``) takes ``snapshot()`` at each edge of the
window, as ``dag_counters.py``'s and ``contract_counters.py``'s drivers do;
the readers ``layers/merkle_fused_call_share.py`` and
``layers/sm_admission_sync_ms_per_call.py`` and the driver's two numbers of
``correct`` work on the difference. By op:

- ``fisco_device_items_total{op[,hasher]}``: ``items``, or ``items_<hasher>``
  where the series names one (the merkle programs': a level hashed under
  keccak on an SM chain would show as ``items_keccak256`` beside ``items_sm3``);
- ``fisco_device_op_seconds_total{op}``: ``ms``, the host's wall in the call;
- ``fisco_device_dispatch_path_total{op,path}``: ``calls_<path>`` (admission's
  ``device`` / ``native`` legs; a merkle tree's ``fused`` / ``levels``);
- the sums of ``fisco_device_phase_ms{op,phase}``: ``<phase>_ms``.

A counter the program does not have is absent here, and its reader None."""

from __future__ import annotations

from benchmark.mesh_counters import _labels

OPS = ("admission", "admission_sm", "admission_native", "sm3", "merkle_root", "merkle_tree",
       "sm2_verify")
MERKLE_OPS = ("merkle_root", "merkle_tree")


def snapshot() -> dict:
    try:
        from fisco_bcos_tpu.observability.device import DEVICE_PHASE_BUCKETS_MS
        from fisco_bcos_tpu.utils.metrics import REGISTRY
    except ImportError:
        return {}
    by_op: dict[str, dict[str, float]] = {}

    def add(op: str, key: str, value: float) -> None:
        if op in OPS:
            row = by_op.setdefault(op, {})
            row[key] = row.get(key, 0.0) + value

    for name, v in REGISTRY.counters_matching("fisco_device_items_total{").items():
        labels = _labels(name)  # the merkle programs' series say their hasher
        add(labels["op"], "_".join(filter(None, ("items", labels.get("hasher")))), v)
    for name, v in REGISTRY.counters_matching("fisco_device_op_seconds_total{").items():
        add(_labels(name)["op"], "ms", v * 1e3)
    for name, v in REGISTRY.counters_matching("fisco_device_dispatch_path_total{").items():
        labels = _labels(name)
        add(labels["op"], "calls_" + labels["path"], v)
    phases = REGISTRY.histogram(
        "fisco_device_phase_ms", buckets=DEVICE_PHASE_BUCKETS_MS).snapshot()
    for labels, (_cum, s, _n) in phases.items():
        labels = dict(labels)
        add(labels.get("op", ""), labels.get("phase", "") + "_ms", s)
    return by_op


def window(cell, op: str, key: str):
    """The window's delta of one of ``snapshot``'s counters from the snapshots
    the driver left on ``cell`` (a series first seen inside the window counts
    from 0, one that never moved is 0), or None where it took none."""
    before, after = getattr(cell, "sm0", None), getattr(cell, "sm1", None)
    if before is None or after is None:
        return None
    return after.get(op, {}).get(key, 0.0) - before.get(op, {}).get(key, 0.0)

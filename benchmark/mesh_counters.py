"""The mesh leg's counters, read for the window: whether a block's admission
went out over the host's chips, and what putting its operands there cost.

The driver (``drivers/admit_mesh.py``) takes ``snapshot()`` at each edge of
the window, as the harness does with ``counters.py``'s; the readers under
``layers/mesh_*.py`` and ``layers/shard_*.py`` work on the difference:

- ``fisco_device_mesh_calls_total{op,devices}``: calls that went out over a
  mesh of ``devices`` and whose answer reached the host (one of ``correct``'s
  numbers in the four-chip cell: a block that fell to one chip, or that the
  host loop answered for, adds nothing);
- ``fisco_device_items_total{op="admission*"}``: lanes the device admission
  programs were given, those of the sharded programs (``*_sharded``) apart;
- the sums of ``fisco_device_phase_ms{op="admission*_sharded",phase}``: the
  mesh leg's phases, of which ``place`` is the operands put on the mesh, one
  shard a device, and waited for (the driver prints all of them per block to
  standard error: ``mesh leg phases, ms per block``).

A program that has none of them gives zeros, and a reader None."""

from __future__ import annotations

_CALLS = "fisco_device_mesh_calls_total{"
_ITEMS = 'fisco_device_items_total{op="admission'


def _labels(name: str) -> dict:
    inside = name[name.index("{") + 1:name.rindex("}")]
    return dict(part.split("=", 1) for part in inside.replace('"', "").split(","))


def snapshot() -> dict:
    try:
        from fisco_bcos_tpu.observability.device import DEVICE_PHASE_BUCKETS_MS
        from fisco_bcos_tpu.utils.metrics import REGISTRY
    except ImportError:
        return {}
    calls: dict[str, float] = {}  # "<op>/<devices>" -> calls
    for name, v in REGISTRY.counters_matching(_CALLS).items():
        labels = _labels(name)
        calls[f"{labels['op']}/{labels['devices']}"] = v
    lanes = {
        _labels(name)["op"]: v for name, v in REGISTRY.counters_matching(_ITEMS).items()
        if 'op="admission_native"' not in name
    }
    phases = REGISTRY.histogram(
        "fisco_device_phase_ms", buckets=DEVICE_PHASE_BUCKETS_MS).snapshot()
    phase_ms: dict[str, float] = {}
    for labels, (_cum, s, _n) in phases.items():
        labels = dict(labels)
        op = labels.get("op", "")
        if op.startswith("admission") and op.endswith("_sharded"):
            phase_ms[labels["phase"]] = phase_ms.get(labels["phase"], 0.0) + s
    return {
        "calls": calls,
        "device_lanes": sum(lanes.values()),
        "sharded_lanes": sum(v for op, v in lanes.items() if op.endswith("_sharded")),
        "phase_ms": phase_ms,
    }


def mesh_calls(before: dict, after: dict, op: str, devices: int) -> float:
    """The delta of the calls of ``op`` that went out over ``devices``."""
    key = f"{op}/{devices}"
    return after.get("calls", {}).get(key, 0.0) - before.get("calls", {}).get(key, 0.0)


def phase_ms(before: dict, after: dict) -> dict[str, float]:
    """The delta of the mesh leg's phase sums, by phase."""
    was = before.get("phase_ms", {})
    return {k: v - was.get(k, 0.0) for k, v in after.get("phase_ms", {}).items()}


def window(ctx, key: str):
    """The window's delta of ``key`` (a count of lanes, or ``<phase>_ms``) from
    the snapshots the driver left on the cell, or None where it took none."""
    before, after = getattr(ctx.cell, "mesh0", None), getattr(ctx.cell, "mesh1", None)
    if not before or not after:
        return None
    if key.endswith("_ms"):
        return phase_ms(before, after).get(key[:-3], 0.0)
    return after[key] - before[key]

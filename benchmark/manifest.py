"""BENCHMARK.json and the files it names. Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its own,
found here by name: a later PR adds a cell by adding files and entries, and
edits nothing that is there."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re

# every function below takes ``root``; left out, it is ``ROOT`` as it stands when
# the call is made, so a test can point the whole harness at a copy of the tree
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TRAFFIC_EXTS = (".json", ".jsonl", ".toml", ".txt", ".csv")
TINY_KEY = "tiny"


def load(root: str | None = None) -> dict:
    with open(os.path.join(root or ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(manifest: dict, workload: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")


def resolve(manifest: dict, workload: str, root: str | None = None) -> tuple[dict, dict, dict]:
    """-> the cell's ``workloads`` entry, its configuration, its traffic mix."""
    entry = cell(manifest, workload)
    return entry, config_of(manifest, entry["config"], root), traffic_of(entry["traffic"], root)


def config_of(manifest: dict, name: str, root: str | None = None) -> dict:
    entry = next(c for c in manifest["configs"] if c["name"] == name)
    with open(os.path.join(root or ROOT, entry["file"])) as f:
        return json.load(f)


def traffic_path(traffic: str, root: str | None = None) -> str:
    for ext in TRAFFIC_EXTS:
        path = os.path.join(root or ROOT, "benchmark", "traffic", traffic + ext)
        if os.path.exists(path):
            return path
    raise SystemExit(f"no traffic file benchmark/traffic/{traffic}.*")


def _mix(traffic: str, root: str | None) -> dict:
    with open(traffic_path(traffic, root)) as f:
        return json.load(f)


def traffic_of(traffic: str, root: str | None = None) -> dict:
    """The mix as a run is given it. Its ``tiny`` object is the rehearsals',
    never a run's: it is left out here, so no driver or generator sees it."""
    mix = _mix(traffic, root)
    mix.pop(TINY_KEY, None)
    return mix


def tiny_traffic_of(traffic: str, root: str | None = None) -> dict:
    """The mix at the size a CPU rehearsal can hold: its own ``tiny`` sizes laid
    over it. A rehearsal puts this in ``traffic_of``'s place
    (``monkeypatch.setattr(manifest, "traffic_of", manifest.tiny_traffic_of)``),
    so a new mix brings its tiny sizes in its one file and no test is edited."""
    mix = _mix(traffic, root)
    if not isinstance(mix.get(TINY_KEY), dict):
        raise SystemExit(f"benchmark/traffic/{traffic}.* states no tiny sizes ({TINY_KEY!r})")
    return dict(mix, **mix.pop(TINY_KEY))


def metrics_of(manifest: dict, group: str, workload: str) -> list[dict]:
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports:
    those that list it, and those that list no cells at all."""
    return [
        m for m in manifest[group]
        if "workloads" not in m or workload in m["workloads"]
    ]


def reader_path(metric: str, root: str | None = None) -> str:
    """``admission_ms_per_block.flood`` is read by
    ``benchmark/layers/admission_ms_per_block.py``: the suffix after the first
    dot splits a quantity by the end-to-end metric it moves, not by reader."""
    return os.path.join(root or ROOT, "benchmark", "layers", metric.split(".", 1)[0] + ".py")


def reader_of(metric: str, root: str | None = None):
    """The metric's ``read(ctx) -> float | None``; None means nothing to
    read in this run, and the harness leaves the metric out of the line."""
    path = reader_path(metric, root)
    if not os.path.exists(path):
        raise SystemExit(f"no reader {os.path.relpath(path, root or ROOT)} for metric {metric!r}")
    spec = importlib.util.spec_from_file_location("_layer_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def driver_of(config: dict):
    return importlib.import_module("benchmark.drivers." + config["driver"])

"""The benchmark's manifest and its trace reduction, checked without a device:
BENCHMARK.json resolves to files by name, a cell can be added as new files
plus entries, and the trace-to-metrics code gives the numbers an independent
count gives on the small recorded trace kept beside this file."""

import io
import json
import os
import re
import shutil

import pytest

import manifest_rules as rules
from benchmark import manifest, run, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
DOC = manifest.load()
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _metrics(group):
    return [pytest.param(m, id=m["name"]) for m in DOC[group]]


def manifest_holds(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert doc["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= doc["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (doc["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert len(json.dumps(doc)) < 64 * 1024
    for path in doc["paths"]:
        assert os.path.isdir(os.path.join(manifest.ROOT, path))
        for _dir, _subdirs, files in os.walk(os.path.join(manifest.ROOT, path)):
            if "__pycache__" in _dir:
                continue
            for name in files:
                assert re.fullmatch(r"[A-Za-z0-9_.\-]+", name), name
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= max(1, len(doc["workloads"]) // 2)


def cell_holds(doc, cell):
    """The cell resolves to its files by name; its mix states the tiny sizes a
    rehearsal runs it at, and they never reach a run."""
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert cell["chips"] in (1, 4) and 0 < len(cell["why"]) <= 200
    for key in ("name", "config", "traffic"):
        assert manifest.NAME_RE.match(cell[key])
    entry = next(c for c in doc["configs"] if c["name"] == cell["config"])
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith("benchmark/configs/") and len(entry["source"]) <= 200
    config = manifest.config_of(doc, cell["config"])
    assert config["reduced"] == entry["reduced"] and config["source"] == entry["source"]
    assert all(key in config for key in entry["reduced"]), "reduced names keys of the file"
    assert config["guarantees"], "the configuration states its guarantees"
    assert hasattr(manifest.driver_of(config), "Cell")
    traffic = manifest.traffic_of(cell["traffic"])
    assert traffic["trace_blocks"] >= 1 and manifest.TINY_KEY not in traffic
    tiny = manifest.tiny_traffic_of(cell["traffic"])
    assert set(tiny) == set(traffic) and tiny != traffic, "tiny sizes of the mix's own keys"
    reported = {m["name"] for m in manifest.metrics_of(doc, "end_to_end", cell["name"])}
    assert "setup_s" in reported and len(reported) >= 2
    layers = manifest.metrics_of(doc, "per_layer", cell["name"])
    assert layers
    for m in layers:
        assert m["moves"] in reported, f"{m['name']} moves a metric {cell['name']} does not report"
        assert callable(manifest.reader_of(m["name"]))


def metric_holds(doc, metric):
    end_to_end = metric in doc["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert manifest.NAME_RE.match(metric["name"]) and manifest.UNIT_RE.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["moves"] in {m["name"] for m in doc["end_to_end"]}
        if "workloads" in metric:
            rules.list_holds(doc, metric, ())
    cells = {w["name"] for w in doc["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    assert names.count(metric["name"]) == 1


def test_manifest_has_exactly_the_contracts_keys():
    manifest_holds(DOC)


@pytest.mark.parametrize("cell", [pytest.param(w, id=w["name"]) for w in DOC["workloads"]])
def test_cell_resolves_to_its_files_by_name(cell):
    cell_holds(DOC, cell)


@pytest.mark.parametrize("metric", _metrics("end_to_end") + _metrics("per_layer"))
def test_metric_entry_is_well_formed(metric):
    metric_holds(DOC, metric)


def test_a_cell_is_added_as_files_and_entries_with_no_edit(tmp_path):
    """A configuration, a traffic mix and a per-layer metric each arrive as a
    new file plus one entry; nothing that is there is touched."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(manifest.ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*") if p.is_file()}
    config = dict(manifest.config_of(DOC, "air4-transfer"), name="air7-transfer", replicas=7)
    (tmp_path / "benchmark/configs/air7-transfer.json").write_text(json.dumps(config))
    traffic = dict(manifest.traffic_of("paced"), batch_txs=256, tick_s=0.8)
    (tmp_path / "benchmark/traffic/paced256.json").write_text(json.dumps(traffic))
    (tmp_path / "benchmark/layers/blocks_in_window.py").write_text(
        "def read(ctx):\n    return float(len(ctx.cell.series)) or None\n")
    doc = json.loads(json.dumps(DOC))
    doc["configs"].append({"name": "air7-transfer", "source": config["source"] + " n=7",
                           "file": "benchmark/configs/air7-transfer.json",
                           "reduced": config["reduced"], "why": "seven replicas"})
    doc["workloads"].append({"name": "air7-transfer.paced256", "config": "air7-transfer",
                             "traffic": "paced256", "chips": 1, "why": "smaller batches"})
    doc["end_to_end"][2]["workloads"].append("air7-transfer.paced256")
    doc["per_layer"].append({"name": "blocks_in_window.paced256", "unit": "count",
                             "better": "higher", "source": "host_clock", "layer": "Client",
                             "moves": "commit_p50_ms", "workloads": ["air7-transfer.paced256"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    doc = manifest.load(root)
    cell = manifest.cell(doc, "air7-transfer.paced256")
    assert manifest.config_of(doc, cell["config"], root)["replicas"] == 7
    assert manifest.traffic_of(cell["traffic"], root)["batch_txs"] == 256
    layers = manifest.metrics_of(doc, "per_layer", cell["name"])
    assert [m["name"] for m in layers] == ["blocks_in_window.paced256"]
    ctx = type("Ctx", (), {"cell": type("C", (), {"series": [1, 2, 3]})})
    assert manifest.reader_of(layers[0]["name"], root)(ctx) == 3.0
    assert {m["name"] for m in manifest.metrics_of(doc, "end_to_end", cell["name"])} == {
        "commit_p50_ms", "setup_s"}
    assert all(p.read_bytes() == data for p, data in before.items())


def test_a_mix_a_cell_and_an_entry_arrive_as_new_files_and_appended_entries(tmp_path, monkeypatch):
    """The witness of the rule the tests of this directory hold the manifest to. In
    a copy of the tree a made-up mix (one new file, with its ``tiny``), a
    configuration under it, its cell, that cell appended to one ``.flood`` list and
    one entry appended to ``per_layer`` with its reader arrive as new files and
    appended entries. Every test file's ``manifest_rule`` holds on the copy, and the
    made-up cell's tiny rehearsal runs to ``correct`` and reads both metrics."""
    import test_commit_moved_row_share
    import test_contract_cell
    import test_dag_framed_share
    import test_execute_counters
    import test_program_spans
    import test_sm_chain_cell
    import test_stage_parts
    import test_tiled_lane_share

    for path in DOC["paths"]:
        shutil.copytree(os.path.join(manifest.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    cell = "air4-made-up.surge"
    surge = dict(manifest.traffic_of("flood"), batch_txs=500, corpus_batches=240,
                 loop="backlog: half blocks back to back (made up)",
                 tiny={"batch_txs": 8, "corpus_batches": 3})
    (tmp_path / "benchmark/traffic/surge.json").write_text(json.dumps(surge))
    config = dict(manifest.config_of(DOC, "air4-transfer"), name="air4-made-up")
    config["source"] += " (made up)"
    (tmp_path / "benchmark/configs/air4-made-up.json").write_text(json.dumps(config))
    (tmp_path / "benchmark/layers/made_up_blocks_in_window.py").write_text(
        "def read(ctx):\n    return float(ctx.cell.window_blocks) or None\n")
    doc = json.loads(json.dumps(DOC))
    doc["configs"].append({"name": "air4-made-up", "source": config["source"],
                           "file": "benchmark/configs/air4-made-up.json",
                           "reduced": config["reduced"], "why": "made up"})
    doc["workloads"].append({"name": cell, "config": "air4-made-up", "traffic": "surge",
                             "chips": 1, "why": "half blocks back to back (made up)"})
    rules.reporting(doc, "committed_tps").append(cell)
    rules.entry_of(doc, "exec_loop_ms_per_block.flood")["workloads"].append(cell)
    doc["per_layer"].append({"name": "made_up_blocks_in_window", "unit": "count",
                             "better": "higher", "source": "program_counter", "layer": "Client",
                             "moves": "committed_tps", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))  # the whole harness, at the copy
    doc = manifest.load()
    manifest_holds(doc)
    for entry in doc["workloads"]:
        cell_holds(doc, entry)
    for metric in doc["end_to_end"] + doc["per_layer"]:
        metric_holds(doc, metric)
    for module in (test_commit_moved_row_share, test_contract_cell, test_dag_framed_share,
                   test_execute_counters, test_program_spans, test_sm_chain_cell,
                   test_stage_parts, test_tiled_lane_share):
        module.manifest_rule(doc)
    assert manifest.traffic_of("surge")["batch_txs"] == 500

    monkeypatch.setattr(manifest, "traffic_of", manifest.tiny_traffic_of)
    args = run.parse(["--workload", cell, "--seed", str(2**31 + 47), "--seconds", "0.5",
                      "--trace", "1"])
    line = run.run(args, require_chip=False, out=io.StringIO())
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"exec_loop_ms_per_block.flood", "made_up_blocks_in_window"}
    assert line["metrics"]["exec_loop_ms_per_block.flood"]["value"] > 0.0
    assert line["metrics"]["made_up_blocks_in_window"]["value"] >= 1.0
    assert line["metrics"]["made_up_blocks_in_window"]["unit"] == "count"
    assert all(p.read_bytes() == data for p, data in before.items())


# -- the trace reduction on the recorded trace --------------------------------


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def _count_busy_ns(events, lo, hi):
    """Independent of trace_reduce.union: sweep over sorted edges."""
    edges = []
    for e in events:
        a, b = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    busy = depth = 0
    last = None
    for t, step in sorted(edges):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_reduction_of_the_recorded_trace(recorded):
    red = trace_reduce.reduce_trace(recorded["events"])
    mark = next(e for e in recorded["events"] if e["name"] == trace_reduce.WINDOW_SPAN)
    lo, hi = mark["start_ns"], mark["start_ns"] + mark["dur_ns"]
    modules = [e for e in recorded["events"]
               if trace_reduce.is_device_plane(e["plane"]) and e["line"] == "XLA Modules"]
    busy = _count_busy_ns(modules, lo, hi)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert red["busy_s"] == pytest.approx(busy / 1e9, rel=1e-12)
    assert red["idle_share"] == pytest.approx(1 - busy / (hi - lo), rel=1e-9)
    assert 0 < red["idle_share"] < 1
    # per-program time: the modules line, fingerprints stripped
    assert modules and all("(" not in name for name in red["program_s"])
    for name, seconds in red["program_s"].items():
        mine = [e for e in modules if trace_reduce.program_name(e["name"]) == name]
        assert seconds == pytest.approx(_count_busy_ns(mine, lo, hi) / 1e9, rel=1e-9)
        assert red["calls"][name] == sum(
            1 for e in mine if e["start_ns"] < hi and e["start_ns"] + e["dur_ns"] > lo)
    # every idle nanosecond is attributed once, to a bench.* span or to none
    assert sum(red["gaps_s"].values()) == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-9)
    assert set(red["gaps_s"]) <= {e["name"] for e in recorded["events"]} | {"(no span)"}
    # what the chip run that recorded it printed (PERF.md, PR 23)
    want = recorded["expected"]
    assert red["busy_s"] == pytest.approx(want["busy_s"]) == pytest.approx(0.194254102)
    assert red["window_s"] == pytest.approx(want["window_s"]) == pytest.approx(0.700205078)
    assert max(red["gaps_s"], key=red["gaps_s"].get) == want["top_gap"] == "bench.seal_and_submit"
    assert red["calls"]["jit__admission_packed"] == want["admission_calls"] == 4
    top = trace_reduce.breakdown(red)
    assert top["device_ops"][0][0] == "jit__admission_packed"
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10
    assert top["device_ops"] == sorted(top["device_ops"], key=lambda kv: -kv[1])


def test_reduction_small_cases():
    assert trace_reduce.union([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert trace_reduce.program_name("jit__admission_packed(812345)") == "jit__admission_packed"
    assert trace_reduce.is_device_plane("/device:TPU:0")
    assert not trace_reduce.is_device_plane("/host:CPU")
    ev = lambda plane, line, name, a, d: {  # noqa: E731
        "plane": plane, "line": line, "name": name, "start_ns": a, "dur_ns": d}
    events = [
        ev("/host:CPU", "python", trace_reduce.WINDOW_SPAN, 0, 1000),
        ev("/host:CPU", "python", "bench.submit_batch", 0, 400),
        ev("/host:CPU", "python", "bench.seal_and_submit", 400, 600),
        ev("/device:TPU:0", "XLA Ops", "while.1", 100, 200),
        ev("/device:TPU:0", "XLA Ops", "fusion.2", 250, 100),  # overlaps while.1 by 50
        ev("/device:TPU:0", "XLA Modules", "jit_step(7)", 100, 250),
        ev("/device:TPU:0", "XLA Ops", "fusion.3", 900, 300),  # runs past the window
        ev("/device:TPU:0", "XLA Modules", "jit_hash(9)", 900, 300),
    ]
    red = trace_reduce.reduce_trace(events)
    assert red["busy_s"] == pytest.approx(350e-9) and red["window_s"] == pytest.approx(1e-6)
    assert red["program_s"] == pytest.approx({"jit_step": 250e-9, "jit_hash": 100e-9})
    assert red["gaps_s"] == pytest.approx(
        {"bench.submit_batch": 150e-9, "bench.seal_and_submit": 500e-9})
    assert trace_reduce.reduce_trace(events[1:]) is None  # no traced window: nothing to read
    assert trace_reduce.reduce_trace(events[:3]) is None  # no device event

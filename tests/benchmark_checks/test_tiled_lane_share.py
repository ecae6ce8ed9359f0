"""The reader of ``tiled_lane_share`` (``benchmark/layers/tiled_lane_share.py``),
checked without a device: on registries built by hand, on a program without
the counter (the parent), and on the counter as ``device_span`` keeps it."""

import pytest

import manifest_rules as rules
from benchmark import manifest
from fisco_bcos_tpu.utils import metrics

TILED = "fisco_device_tiled_items_total"
ITEMS = "fisco_device_items_total"


def read():
    return manifest.reader_of("tiled_lane_share")(None)


def call(registry, op, lanes, tiled):
    """What a device call of `op` over `lanes` leaves: its items, and where
    its program has a plan the tiled items (0 for one tile)."""
    registry.counter_add(f'{ITEMS}{{op="{op}"}}', lanes)
    if tiled is not None:
        registry.counter_add(f'{TILED}{{op="{op}"}}', lanes if tiled else 0.0)


@pytest.fixture
def registry(monkeypatch):
    fresh = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", fresh)
    return fresh


def manifest_rule(doc):
    entry = rules.entry_of(doc, "tiled_lane_share")
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "tiled_lane_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Device programs", "moves": "verify_tps"}
    # the cells the list had when this file was written: still on it, at the front
    rules.list_holds(doc, entry, ["verify10k.stream", "verify10k-quad.stream"])


def test_the_entry_is_a_counter_of_the_device_programs_in_the_two_stream_cells():
    manifest_rule(manifest.load())
    assert manifest.reader_path("tiled_lane_share").endswith(
        "benchmark/layers/tiled_lane_share.py")


@pytest.mark.parametrize("calls,want", [
    # the four-chip cell: every block's lanes go to a program planned in tiles
    ([("admission_sharded", 10000, True)] * 3, 100.0),
    # the one-chip cell: 10,240 lanes are one tile
    ([("admission", 10000, False)] * 3, 0.0),
    ([("admission_sharded", 3000, True), ("admission", 1000, False)], 75.0),
    # the SM body counts like the other; the native loop's lanes are not the device's
    ([("admission_sm_sharded", 500, True), ("admission_sm", 500, False),
      ("admission_native", 9000, None)], 50.0),
], ids=["quad", "one_chip", "mixed", "sm_and_native"])
def test_share_is_the_tiled_lanes_over_all_device_admission_lanes(registry, calls, want):
    for op, lanes, tiled in calls:
        call(registry, op, lanes, tiled)
    assert read() == pytest.approx(want)


@pytest.mark.parametrize("build", [
    lambda r: None,
    lambda r: call(r, "admission", 10000, None),  # the parent: items, no such counter
    lambda r: call(r, "admission_native", 10000, None),
    lambda r: call(r, "admission", 0, False),  # nothing went to the device yet
], ids=["empty", "parent", "native_only", "no_lanes"])
def test_reader_gives_none_where_there_is_nothing_to_read(registry, build):
    build(registry)
    assert read() is None


@pytest.mark.parametrize("tiles,tile_lanes,want", [(2, 1280, 30.0), (1, 10240, 0.0)],
                         ids=["tiled", "one_tile"])
def test_device_span_adds_a_tiled_calls_items_and_nothing_for_one_tile(
        registry, tiles, tile_lanes, want):
    from fisco_bcos_tpu.observability import TRACER, device

    name = f'{TILED}{{op="admission_probe"}}'
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(device._metrics, "REGISTRY", registry)
        with device.device_span("admission_probe", 30, shape_key=(32, 2)) as sp:
            sp.plan(tiles, tile_lanes)
        with device.device_span("merkle_probe", 30, shape_key=32):
            pass  # no plan: no series
    assert registry.counters_matching(TILED) == {name: want}
    assert registry.counters_matching(f'{ITEMS}{{op="admission_probe"}}') == {
        f'{ITEMS}{{op="admission_probe"}}': 30.0}
    record = [r for r in TRACER.spans() if r.name == "device.admission_probe"][-1]
    assert record.attrs["tiles"] == tiles and record.attrs["tile_lanes"] == tile_lanes
    assert read() == (100.0 if tiles > 1 else 0.0)

"""The reader of ``commit_moved_row_share.*`` (``benchmark/layers/commit_moved_row_share.py``),
checked without a device: on registries built by hand, on a program without
the counter (the parent), and on a block committed through a scheduler here."""

import pytest

import manifest_rules as rules
from benchmark import manifest
from fisco_bcos_tpu.utils import metrics

COUNTER = "fisco_storage_prepare_rows_total"
LAYER = "Sealer, PBFT, scheduler, storage"


def read(name="commit_moved_row_share.flood"):
    return manifest.reader_of(name)(None)


def prepared(registry, mode, rows):
    registry.counter_add(f'{COUNTER}{{mode="{mode}"}}', rows)


@pytest.fixture
def registry(monkeypatch):
    fresh = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", fresh)
    return fresh


# the cells each list had when this file was written: still on it, at the front
FRONT = {"commit_moved_row_share.flood": ["air4-transfer.flood", "air4-catchup.backlog",
                                          "air4-dagtransfer.flood"],
         "commit_moved_row_share.paced": ["air4-transfer.paced"]}


def manifest_rule(doc):
    for name, front in FRONT.items():
        entry = rules.entry_of(doc, name)
        assert {k: v for k, v in entry.items() if k != "workloads"} == {
            "name": name, "unit": "%", "better": "higher", "source": "program_counter",
            "layer": LAYER, "moves": rules.SUFFIX_MOVES[name.partition(".")[2]]}
        rules.list_holds(doc, entry, front)


def test_the_two_entries_are_counters_of_the_schedulers_layer_in_the_chain_cells():
    manifest_rule(manifest.load())
    # one reader for both
    assert manifest.reader_path("commit_moved_row_share.flood") == \
        manifest.reader_path("commit_moved_row_share.paced")


@pytest.mark.parametrize("moved,copied,want", [
    ([3007, 3007, 3007, 3007], [], 100.0),  # a block's four replicas, every row lent
    ([3000], [1000], 75.0),
    ([], [3007], 0.0),  # a write-set that has only traverse()
])
def test_share_is_the_moved_rows_over_all_prepared_rows(registry, moved, copied, want):
    for rows in moved:
        prepared(registry, "moved", rows)
    for rows in copied:
        prepared(registry, "copied", rows)
    assert read() == read("commit_moved_row_share.paced") == pytest.approx(want)


@pytest.mark.parametrize("build", [
    lambda r: None,  # the parent: a program without the counter
    lambda r: r.counter_add("fisco_storage_prepare_latency_ms", 3.0),
    lambda r: (prepared(r, "moved", 0), prepared(r, "copied", 0)),  # nothing prepared yet
], ids=["parent", "other_storage_metrics", "nothing_prepared"])
def test_reader_gives_none_where_there_is_nothing_to_read(registry, build):
    build(registry)
    assert read() is None and read("commit_moved_row_share.paced") is None


def test_reader_on_blocks_committed_here(registry):
    """A scheduler over ``MemoryStorage``, the cells' stated storage: every
    row of every block is lent, so the share is 100."""
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from fisco_bcos_tpu.storage import interfaces
    from test_executor import Env

    assert interfaces.REGISTRY is not registry  # bound at import: count on the fresh one
    env = Env()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(interfaces, "REGISTRY", registry)
        for b in range(2):
            env.run_block([
                env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", f"u{b}-{i}", 10)
                for i in range(4)
            ])
    rows = registry.counters_matching(COUNTER)
    # a series appears when its mode is first counted: nothing was copied
    assert list(rows) == [f'{COUNTER}{{mode="moved"}}'] and sum(rows.values()) >= 2 * 12
    assert read() == 100.0

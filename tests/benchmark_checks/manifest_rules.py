"""What the tests of this directory hold a per-layer entry's ``workloads`` list
to: a rule read from the manifest, not a literal. A later PR appends a cell to
a list, or an entry to ``per_layer``, and edits no file here.

The rule of a list (``list_holds``):

- the cells the entry had when its test was written are still on it, in their
  order, at the front (entries and cells are appended, nothing is moved), and
  no cell is on it twice;
- every listed cell reports the entry's ``moves`` metric, that is, stands on
  that end-to-end metric's own ``workloads`` (one without the key is reported
  by every cell);
- a suffix says which end-to-end metric: ``.flood`` moves ``committed_tps``,
  ``.paced`` moves ``commit_p50_ms``.

Each test file that holds entries of the manifest has a ``manifest_rule(doc)``
that applies it to its own; ``test_benchmark_manifest.py`` runs every one of
them on a copy of the tree that a made-up mix, configuration, cell and entry
have joined."""

from benchmark import manifest

SUFFIX_MOVES = {"flood": "committed_tps", "paced": "commit_p50_ms"}
# the 22 ``.flood`` lists that split a block, as PR 47 found them: the stage clock's 21
# (execution, the 2PC, admission, the collector's pauses and the chip's idle time by
# stage) and the 2PC's moved rows. The chain cells under the flood stand on each.
BLOCK_SPLIT = tuple(quantity + ".flood" for quantity in (
    "exec_loop_ms_per_block", "exec_state_root_ms_per_block", "exec_txs_root_ms_per_block",
    "exec_receipts_root_ms_per_block", "exec_roots_wait_ms_per_block", "exec_other_ms_per_block",
    "commit_prewrite_ms_per_block", "commit_prepare_ms_per_block", "commit_write_ms_per_block",
    "commit_book_ms_per_block", "admit_static_ms_per_block", "admit_verify_ms_per_block",
    "admit_insert_ms_per_block", "admit_gossip_ms_per_block", "gc_in_execute_ms_per_block",
    "gc_in_commit_ms_per_block", "gc_in_admission_ms_per_block", "idle_in_execute_ms_per_block",
    "idle_in_commit_ms_per_block", "idle_in_admission_ms_per_block",
    "idle_elsewhere_ms_per_block", "commit_moved_row_share"))


def cells(doc) -> list[str]:
    return [w["name"] for w in doc["workloads"]]


def entry_of(doc, name: str) -> dict:
    (entry,) = [m for m in doc["per_layer"] if m["name"] == name]
    return entry


def reporting(doc, metric: str) -> list[str]:
    """The cells that report the end-to-end metric ``metric``."""
    (entry,) = [m for m in doc["end_to_end"] if m["name"] == metric]
    return entry.get("workloads", cells(doc))


def listing(doc, cell: str) -> list[str]:
    """The per-layer entries whose ``workloads`` name ``cell``."""
    return [m["name"] for m in doc["per_layer"] if cell in m.get("workloads", ())]


def list_holds(doc, entry: dict, front) -> None:
    listed = entry["workloads"]
    assert listed[:len(front)] == list(front), (entry["name"], listed)
    assert len(set(listed)) == len(listed), (entry["name"], listed)
    assert set(listed) <= set(reporting(doc, entry["moves"])), (entry["name"], entry["moves"])
    suffix = entry["name"].partition(".")[2]
    if suffix in SUFFIX_MOVES:
        assert entry["moves"] == SUFFIX_MOVES[suffix], entry["name"]


def driver_cell(doc, cell: str):
    """The ``Cell`` class of the driver the cell's configuration names."""
    config = manifest.config_of(doc, manifest.cell(doc, cell)["config"])
    return manifest.driver_of(config).Cell


def window_admits(doc, cell: str) -> bool:
    """Whether the cell's window writes admission's spans: its driver says
    where it does not (``drivers/catchup.py``: ``window_admits = False``)."""
    return getattr(driver_cell(doc, cell), "window_admits", True)


def admitting_list_holds(doc, entry: dict, front) -> None:
    """``list_holds`` for an entry that reads admission's spans: the front is
    what it was less the cells whose window admits nothing, and no such cell is
    on the list at all."""
    assert all(window_admits(doc, c) for c in entry["workloads"]), entry["name"]
    list_holds(doc, entry, [c for c in front if window_admits(doc, c)])

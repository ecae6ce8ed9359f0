"""Sealer, PBFT, scheduler, storage: the share of the rows that the backends'
2PC ``prepare`` staged without copying them
(``fisco_storage_prepare_rows_total{mode="moved"}`` over both modes): the
write-set lent its ``Entry`` objects and the slot kept them. Both are the
process's totals since it started, as in ``dag_framed_tx_share``: every block
the cell's process commits goes through the same leg (the opening blocks of
set-up, the warm batches, the window, the traced blocks), and a share does
not need the window's edges. None on a program without the counter, and
where nothing was prepared."""


def read(ctx):
    try:
        from fisco_bcos_tpu.utils.metrics import REGISTRY
    except ImportError:
        return None
    rows = REGISTRY.counters_matching("fisco_storage_prepare_rows_total")
    total = sum(rows.values())
    if not total:
        return None
    return 100.0 * rows.get('fisco_storage_prepare_rows_total{mode="moved"}', 0.0) / total

"""Device programs: lanes handed to a device admission program that runs the
lanes a chip is given in more than one tile
(``fisco_device_tiled_items_total{op="admission*"}``), as a share of the lanes
of all device admission programs (``fisco_device_items_total{op="admission*"}``,
the native loop's left out). The admission body plans its lanes
(``ops/limb.lane_plan``, a function of the lane count alone): 100 in
``verify10k-quad.stream``, whose 2,560 lanes a chip are planned in tiles, and
0 in ``verify10k.stream``, whose 10,240 lanes are one tile: the same blocks
past the mechanism, a bypass shown and not a loss. Both are the process's
totals since it started, as in ``dag_framed_tx_share``: every block the
cell's process admits has the cell's one shape (the warm batches, the
window, the traced blocks), and a share does not need the window's edges.
None on a program without the counter, and where no lane went to the
device."""

_TILED = 'fisco_device_tiled_items_total{op="admission'
_ITEMS = 'fisco_device_items_total{op="admission'


def read(ctx):
    try:
        from fisco_bcos_tpu.utils.metrics import REGISTRY
    except ImportError:
        return None
    tiled = REGISTRY.counters_matching(_TILED)
    lanes = sum(v for name, v in REGISTRY.counters_matching(_ITEMS).items()
                if 'op="admission_native"' not in name)
    if not tiled or not lanes:
        return None
    return 100.0 * sum(tiled.values()) / lanes

"""Client: 95th percentile (nearest rank) of the window's per-batch commit
times, from each batch's due time. With some tens of batches a window this
is about the second-largest reading, which is why it is not end-to-end."""

import math


def read(ctx):
    v = sorted(s["commit_ms"] for s in ctx.cell.series if "commit_ms" in s)
    return v[math.ceil(0.95 * len(v)) - 1] if v else None

"""BlockSync: the window less decode, QC, verification, execution, commit and
serving, per applied block: the gateway's hand-offs, what ``sync.apply_block``
does around execute and commit, status messages.
Rule: ``benchmark/sync_counters.py``."""

from benchmark import sync_counters

PARTS = ("decode_ms", "qc_ms", "verify_ms", "execute_ms", "commit_ms", "serve_request_ms")


def read(ctx):
    parts = [sync_counters.per_block(ctx, key) for key in PARTS]
    if parts[2] is None:  # block sync counted no verification: nothing to subtract from
        return None
    blocks = ctx.cell.sync1["applied"] - ctx.cell.sync0["applied"]
    return (ctx.t1 - ctx.t0) * 1e3 / blocks - sum(p or 0.0 for p in parts)

#!/usr/bin/env python
"""Storage throughput harness — the analog of the reference's
tests/perf/benchmark.cpp (StateStorage vs KeyPageStorage vs RocksDB
read/write CLI, /root/reference/tests/perf/benchmark.cpp:22-100).

Prints one JSON line per (backend, op) with rows/s. Usage:

    python bench_storage.py [N]          # default 20k rows

Backends: StateStorage overlay (the executor's working set),
KeyPageStorage (page-packed key layout), SqliteStorage (the durable
RocksDB analog, batch-committed).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

from fisco_bcos_tpu.storage.entry import Entry
from fisco_bcos_tpu.storage.keypage import KeyPageStorage
from fisco_bcos_tpu.storage.memory_storage import MemoryStorage
from fisco_bcos_tpu.storage.sqlite_storage import SQLiteStorage
from fisco_bcos_tpu.storage.state_storage import StateStorage

TABLE = "t_bench"


def _emit(backend: str, op: str, n: int, dt: float) -> dict:
    rec = {
        "metric": f"storage_{backend}_{op}_rows_per_s",
        "value": round(n / dt, 1),
        "unit": "rows/s",
        "n": n,
        "backend": backend,
        "op": op,
        "platform": "host",  # pure host CPU: no JAX, no device
    }
    print(json.dumps(rec), flush=True)
    return rec


def _bench(backend: str, store, n: int, batched=None, results=None) -> None:
    keys = [b"key-%08d" % i for i in range(n)]
    entries = [Entry({"value": b"v" * 32 + b"%08d" % i}) for i in range(n)]
    t0 = time.perf_counter()
    if batched is not None:
        batched(TABLE, list(zip(keys, entries)))
    else:
        for k, e in zip(keys, entries):
            store.set_row(TABLE, k, e)
    rec = _emit(backend, "write", n, time.perf_counter() - t0)
    if results is not None:
        results.append(rec)
    t0 = time.perf_counter()
    miss = 0
    for k in keys:
        if store.get_row(TABLE, k) is None:
            miss += 1
    dt = time.perf_counter() - t0
    assert miss == 0, f"{backend}: {miss} missing rows"
    rec = _emit(backend, "read", n, dt)
    if results is not None:
        results.append(rec)


def run(n: int = 20_000, deadline: float | None = None) -> list[dict]:
    """All three backend legs; under bench.py's ``--only storage`` child
    the monotonic ``deadline`` stops BETWEEN legs, so a slow disk costs
    the remaining legs' lines, never a budget-killed child."""
    results: list[dict] = []

    def expired(leg: str) -> bool:
        if deadline is not None and time.monotonic() > deadline:
            print(f"# bench_storage: deadline before {leg} leg", flush=True)
            return True
        return False

    if not expired("state_storage"):
        _bench(
            "state_storage", StateStorage(MemoryStorage()), n, results=results
        )
    if not expired("keypage"):
        kp = KeyPageStorage(MemoryStorage())
        _bench("keypage", kp, n, batched=kp.set_rows, results=results)
    if not expired("sqlite"):
        with tempfile.TemporaryDirectory() as d:
            sq = SQLiteStorage(os.path.join(d, "bench.db"))
            _bench("sqlite", sq, n, batched=sq.set_rows, results=results)
    return results


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 20_000
    run(n)


if __name__ == "__main__":
    main()

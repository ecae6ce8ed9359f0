"""KeyPageStorage: page packing, splits, 2PC repacking.

Reference: bcos-table/src/KeyPageStorage.cpp.
"""

import random

from fisco_bcos_tpu.storage import MemoryStorage
from fisco_bcos_tpu.storage.keypage import PAGE_TABLE, KeyPageStorage
from fisco_bcos_tpu.storage.entry import Entry, EntryStatus
from fisco_bcos_tpu.storage.interfaces import TwoPCParams


def test_basic_rw_and_delete():
    kp = KeyPageStorage(MemoryStorage(), page_size=4)
    assert kp.get_row("t", b"missing") is None
    kp.set_row("t", b"k1", Entry({"value": b"v1"}))
    kp.set_row("t", b"k2", Entry({"value": b"v2"}))
    assert kp.get_row("t", b"k1").get() == b"v1"
    assert kp.get_row("t", b"k2").get() == b"v2"
    kp.set_row("t", b"k1", Entry({"value": b"v1b"}))  # overwrite
    assert kp.get_row("t", b"k1").get() == b"v1b"
    kp.set_row("t", b"k1", Entry(status=EntryStatus.DELETED))
    assert kp.get_row("t", b"k1") is None
    assert kp.get_primary_keys("t") == [b"k2"]


def test_pages_split_and_stay_sorted():
    inner = MemoryStorage()
    kp = KeyPageStorage(inner, page_size=8)
    keys = [f"key{i:04d}".encode() for i in range(100)]
    shuffled = keys[:]
    random.Random(7).shuffle(shuffled)
    for k in shuffled:
        kp.set_row("acct", k, Entry({"value": b"v" + k}))
    assert kp.get_primary_keys("acct") == sorted(keys)
    for k in keys:
        assert kp.get_row("acct", k).get() == b"v" + k
    # actually paged: far fewer backend rows than keys
    n_pages = len(inner.get_primary_keys(PAGE_TABLE))
    assert 100 / 8 <= n_pages < 100 / 2, n_pages


def test_tables_are_isolated():
    kp = KeyPageStorage(MemoryStorage(), page_size=4)
    kp.set_row("a", b"k", Entry({"value": b"in-a"}))
    kp.set_row("b", b"k", Entry({"value": b"in-b"}))
    assert kp.get_row("a", b"k").get() == b"in-a"
    assert kp.get_row("b", b"k").get() == b"in-b"
    assert kp.get_primary_keys("a") == [b"k"]


def test_2pc_repacks_rows_into_pages():
    kp = KeyPageStorage(MemoryStorage(), page_size=16)
    kp.set_row("s", b"pre", Entry({"value": b"old"}))
    writes = MemoryStorage()
    for i in range(40):
        writes.set_row("s", f"w{i:03d}".encode(), Entry({"value": b"x%d" % i}))
    writes.set_row("s", b"pre", Entry({"value": b"new"}))
    params = TwoPCParams(number=3)
    kp.prepare(params, writes)
    assert kp.get_row("s", b"pre").get() == b"old"  # not visible pre-commit
    kp.commit(params)
    assert kp.get_row("s", b"pre").get() == b"new"
    for i in range(40):
        assert kp.get_row("s", f"w{i:03d}".encode()).get() == b"x%d" % i
    assert len(kp.get_primary_keys("s")) == 41

    # rollback drops the staged write-set
    writes2 = MemoryStorage()
    writes2.set_row("s", b"pre", Entry({"value": b"never"}))
    params2 = TwoPCParams(number=4)
    kp.prepare(params2, writes2)
    kp.rollback(params2)
    assert kp.get_row("s", b"pre").get() == b"new"


def test_traverse_unpacks_pages():
    kp = KeyPageStorage(MemoryStorage(), page_size=4)
    for i in range(10):
        kp.set_row("t", b"k%d" % i, Entry({"value": b"v%d" % i}))
    seen = {(t, k): e.get() for t, k, e in kp.traverse()}
    assert seen[("t", b"k3")] == b"v3" and len(seen) == 10


def test_bulk_set_rows_pages_and_cache_coherence():
    """set_rows batches whole pages (one codec per touched page); the
    decoded-page cache must stay coherent across direct writes, 2PC
    commits (which bypass _save_page), and interleaved reads."""
    kp = KeyPageStorage(MemoryStorage(), page_size=8)
    rows = [(b"k%04d" % i, Entry({"value": b"v%d" % i})) for i in range(100)]
    kp.set_rows("b", rows)
    for i in range(100):
        assert kp.get_row("b", b"k%04d" % i).get() == b"v%d" % i
    # overwrite a slice plus fresh keys in one bulk call (last-wins)
    kp.set_rows(
        "b",
        [(b"k0005", Entry({"value": b"A"})), (b"k0005", Entry({"value": b"B"})),
         (b"k9000", Entry({"value": b"new"}))],
    )
    assert kp.get_row("b", b"k0005").get() == b"B"
    assert kp.get_row("b", b"k9000").get() == b"new"
    assert len(kp.get_primary_keys("b")) == 101
    # 2PC lands through inner.prepare/commit: cached pages must refresh
    assert kp.get_row("b", b"k0042").get() == b"v42"  # warm the cache
    writes = MemoryStorage()
    writes.set_row("b", b"k0042", Entry({"value": b"committed"}))
    params = TwoPCParams(number=9)
    kp.prepare(params, writes)
    kp.commit(params)
    assert kp.get_row("b", b"k0042").get() == b"committed"


def test_head_page_rekey_on_split_keeps_rows_readable():
    """Keys inserted BELOW the table's first registered start accumulate in
    the head page; splitting that page must rekey it to its true min key —
    registering later chunks at starts that sort below the head page's key
    silently orphaned the head rows (round-3 review repro)."""
    kp = KeyPageStorage(MemoryStorage(), page_size=8)
    # seed with a non-minimal key, then bulk-write 20 smaller keys
    rows = [(b"m0", Entry({"value": b"head"}))]
    rows += [(b"a%02d" % i, Entry({"value": b"x%d" % i})) for i in range(20)]
    kp.set_rows("t", rows)
    for i in range(20):
        assert kp.get_row("t", b"a%02d" % i).get() == b"x%d" % i, i
    assert kp.get_row("t", b"m0").get() == b"head"
    assert len(kp.get_primary_keys("t")) == 21
    # same scenario through the per-row path (incremental inserts)
    kp2 = KeyPageStorage(MemoryStorage(), page_size=4)
    kp2.set_row("u", b"zz", Entry({"value": b"tail"}))
    for i in range(10):
        kp2.set_row("u", b"b%02d" % i, Entry({"value": b"y%d" % i}))
    for i in range(10):
        assert kp2.get_row("u", b"b%02d" % i).get() == b"y%d" % i, i
    assert kp2.get_row("u", b"zz").get() == b"tail"
    # and through the 2PC path
    kp3 = KeyPageStorage(MemoryStorage(), page_size=4)
    kp3.set_row("w", b"q5", Entry({"value": b"first"}))
    writes = MemoryStorage()
    for i in range(12):
        writes.set_row("w", b"c%02d" % i, Entry({"value": b"z%d" % i}))
    params = TwoPCParams(number=12)
    kp3.prepare(params, writes)
    kp3.commit(params)
    for i in range(12):
        assert kp3.get_row("w", b"c%02d" % i).get() == b"z%d" % i, i
    assert kp3.get_row("w", b"q5").get() == b"first"
    # traverse must not resurrect tombstoned page rows
    seen = {k for _t, k, _e in kp3.traverse()}
    assert b"q5" in seen and len(seen) == 13


def test_keypage_copy_in_copy_out_discipline_holds():
    """KeyPage pages never alias caller-held entries: mutating the entry
    handed to set_rows, or the entry returned by get_row, must never reach
    the stored page. If this fails, keypage grew an aliasing leak and needs
    a copy at the failing surface."""
    kp = KeyPageStorage(MemoryStorage())
    mine = Entry().set(b"original")
    kp.set_rows("t_pin", [(b"k1", mine)])
    # copy-in: the page holds another object than the caller's
    got = kp.get_row("t_pin", b"k1")
    assert got is not mine
    mine.set(b"mutated-after-set")
    assert kp.get_row("t_pin", b"k1").get() == b"original"
    # copy-out: two reads hand out two objects, neither the page's own
    again = kp.get_row("t_pin", b"k1")
    assert again is not got
    got.set(b"mutated-read")
    assert kp.get_row("t_pin", b"k1").get() == b"original"
    # the 2PC path stages the page's bytes, not the caller's object
    writes = MemoryStorage()
    staged = Entry().set(b"staged")
    writes.set_row("t_pin", b"k2", staged)
    kp.prepare(TwoPCParams(number=1), writes)
    kp.commit(TwoPCParams(number=1))
    staged.set(b"mutated-after-prepare")
    assert kp.get_row("t_pin", b"k2").get() == b"staged"
    assert kp.get_row("t_pin", b"k1").get() == b"original"

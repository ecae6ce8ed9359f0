"""Tiny sizes of the traffic mixes that came after ``test_benchmark_rehearsal.py``
was written: that file keys its sizes by the name of the mix (``TINY``) and
runs every cell of the manifest, so a later mix gives its own here and the
file stays as it is."""

import pytest

LATER_MIXES = {
    # 6 blocks of 8 transactions behind, 3 more for the traced gather
    "backlog": {"batch_txs": 8, "backlog_blocks": 6, "corpus_batches": 9,
                "trace_blocks": 3, "senders": 4},
}


@pytest.fixture(autouse=True)
def tiny_sizes_of_later_mixes(request):
    tiny = getattr(request.module, "TINY", None)
    if isinstance(tiny, dict) and "flood" in tiny:
        for name, sizes in LATER_MIXES.items():
            tiny.setdefault(name, sizes)

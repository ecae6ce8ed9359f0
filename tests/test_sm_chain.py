"""A national-crypto Air chain reaches the fused SM admission: four nodes with
``sm_crypto=True``, batches through ``txpool.submit_batch`` (tool/sm_chain_run.py
at a tiny size); what the nodes acknowledge is what the plain reference gives,
balances match a dict replay, one state root."""

import sys

import pytest

from fisco_bcos_tpu.observability.device import LEDGER

sys.path.insert(0, "tool")


@pytest.mark.parametrize("leg", ["device", "native"])
def test_sm_air_chain_through_submit_batch(leg, monkeypatch):
    import sm_chain_run

    if leg == "device":
        monkeypatch.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")
    else:
        monkeypatch.delenv("FISCO_FORCE_DEVICE_ADMISSION", raising=False)
    LEDGER.reset()
    doc = sm_chain_run.run(blocks=2, batch_txs=8, senders=4, seed=26 + (leg == "device"))
    assert doc["valid_not_acknowledged"] == 0 and doc["committed"] == 24
    assert doc["acks_differing_from_plain_sm"] == 0
    assert doc["balances_differing_from_replay"] == 0
    assert doc["state_roots"] == 1 and len(doc["heights"]) == 1
    # entry node + three replicas on the sync lane, a block
    assert doc["admission_paths"] == {leg: 2 * 4}
    ops = {op for op, _t0, _dur, phases in LEDGER.dispatches() if set(phases) - {"queue"}}
    if leg == "device":
        assert "admission_sm" in ops
        # the batch no longer goes hash -> e -> verify -> address as programs of their own
        assert not ops & {"sm2_verify", "sm2_recover"}
    else:
        assert "admission_sm" not in ops and "admission_native" in LEDGER.max_batches()

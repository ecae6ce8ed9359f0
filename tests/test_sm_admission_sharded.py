"""A fused admission body fanned out over a device mesh (the plane's leg for
merged batches over its threshold) against the single-device program, on the
CPU's forced devices: the same packed bytes, lane for lane. Both suites go
through one construction (parallel.sharding.sharded_admission_packed)."""

import jax
import numpy as np
import pytest

from fisco_bcos_tpu.crypto import admission
from fisco_bcos_tpu.crypto.suite import ecdsa_suite, sm_suite
from fisco_bcos_tpu.observability.device import LEDGER

from test_admission import _signed
from test_sm_admission import _broken, _corpus


def _secp_corpus():
    payloads = [b"sharded secp %02d " % i + b"q" * (i * 19 % 120) for i in range(12)]
    sigs, _pubs = _signed(payloads)
    sigs[3, 32:64] = 0  # s = 0
    sigs[8, :32] = 0  # r = 0
    return payloads, sigs, 2


def _sm_corpus():
    payloads, sigs = _corpus()
    return payloads, _broken(sigs), 6


@pytest.mark.parametrize("suite,corpus,op", [
    (sm_suite, _sm_corpus, "admission_sm"), (ecdsa_suite, _secp_corpus, "admission"),
], ids=["sm2_sm3", "secp256k1_keccak256"])
def test_sharded_body_agrees_with_the_single_device_one(suite, corpus, op, monkeypatch):
    assert len(jax.devices()) >= 4, "conftest pins eight virtual CPU devices"
    body = admission._body_of(suite())
    payloads, sigs, rejected = corpus()
    single = admission._admit_batch_device(payloads, sigs, allow_shard=False, body=body)
    monkeypatch.setenv("FISCO_DEVICE_SHARD_MIN", "8")  # the test bucket (32 lanes) clears it
    monkeypatch.setattr(admission, "_SHARD_CACHE", {})
    LEDGER.reset()
    sharded = admission._admit_batch_device(payloads, sigs, allow_shard=True, body=body)
    assert [d[0] for d in LEDGER.dispatches()] == [op + "_sharded"]
    assert list(admission._SHARD_CACHE) == [(op, len(jax.devices()))]
    for a, b in zip(single, sharded):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (~single[1]).sum() == rejected  # on whichever shard each broken lane fell

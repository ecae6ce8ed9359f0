"""CPU rehearsals of the national-crypto cell at a tiny size (blocks of 16):
no device, no speed. The sound run and the controls come with the cell's name
in ``test_benchmark_rehearsal.py``; here: ``correct`` is false when the timed
path underneath accepts a corrupted SM lane, whichever of the six it is; the
corpus repeats for a seed; a program without the fused SM admission makes the
cell leave at once."""

import io

import numpy as np
import pytest

from benchmark import manifest, refsm, run
from benchmark.generators import sm_signed_payloads

CELL = "sm-verify10k.stream"
SEED = 2**31 + 54321  # the driver's seeds are large


@pytest.fixture(autouse=True)
def tiny_traffic(monkeypatch):
    monkeypatch.setattr(manifest, "traffic_of", manifest.tiny_traffic_of)


def _run():
    out = io.StringIO()
    args = run.parse(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.5", "--trace", "0"])
    return run.run(args, require_chip=False, out=out), out.getvalue()


def _corpus(seed=SEED):
    return sm_signed_payloads.Corpus(dict(manifest.traffic_of("stream")), seed)


@pytest.mark.parametrize("which", range(6), ids=[b.replace(" ", "_") for b in sm_signed_payloads.BROKEN])
def test_correct_is_false_when_the_timed_path_accepts_a_corrupted_sm_lane(which, monkeypatch):
    from fisco_bcos_tpu.crypto import admission

    real = admission.admit_batch
    _block, lanes = _corpus().corrupted()

    def lenient(payloads, sigs, suite=None):
        """Right everywhere but on one broken lane of the corrupted block,
        which it admits with the key the lane carries."""
        senders, ok, pubs, digests = (np.array(a) for a in real(payloads, sigs, suite=suite))
        lane = lanes[which]
        if not ok[lanes].any() and not ok[lane] and (~ok).sum() == 6:
            ok[lane] = True
        return senders, ok, pubs, digests

    monkeypatch.setattr(admission, "admit_batch", lenient)
    line, said = _run()
    assert line["correct"] is False
    assert "compared corrupted_lanes_accepted: 1 (limit 0)  <-- outside" in said


def test_a_sound_run_compares_exactly_and_fails_nothing():
    line, said = _run()
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"verify_tps", "setup_s"}
    compared = [ln.split("compared ")[1] for ln in said.splitlines() if "compared " in ln]
    assert len(compared) == 4 and all(c.endswith(": 0 (limit 0)") for c in compared)


def test_corpus_repeats_for_a_seed_and_the_reference_rejects_the_six():
    a, b, other = _corpus(), _corpus(), _corpus(SEED + 1)
    assert all((x["sigs"] == y["sigs"]).all() and x["payloads"] == y["payloads"]
               for x, y in zip(a.blocks, b.blocks))
    assert not (a.unique["sig"] == other.unique["sig"]).all()
    assert a.unique["sig"].shape == (4, 128) and len(a.blocks[0]["payloads"]) == 16
    block, lanes = a.corrupted()
    assert len(lanes) == len(set(lanes)) == 6 and lanes == b.corrupted()[1]
    for lane in range(16):
        ok = refsm.admit(block["payloads"][lane], bytes(block["sigs"][lane]))[0]
        assert ok == (lane not in lanes)
    # the neighbour's key is a valid point, and the signature is intact beside it
    last = block["sigs"][lanes[5]]
    assert refsm.on_curve(tuple(int.from_bytes(bytes(last[lo:lo + 32]), "big") for lo in (64, 96)))
    assert (last[:64] == a.unique["sig"][block["idx"][lanes[5]], :64]).all()


def test_a_program_without_the_fused_sm_admission_leaves_at_once(monkeypatch, capsys):
    from fisco_bcos_tpu.crypto.suite import CryptoSuite

    monkeypatch.setattr(CryptoSuite, "fused_admission", lambda self: None)
    with pytest.raises(SystemExit) as e:
        _run()
    assert e.value.code == run.RC_NO_PROGRAM
    assert "no fused SM2/SM3 admission" in capsys.readouterr().err

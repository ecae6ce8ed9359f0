"""chip_smoke.py (the on-chip bring-up proof) — what can be pinned on a CPU:

- the orchestrating parent imports neither JAX nor the package, and what any
  other parent may do before spawning a chip child (the package import, the
  static-analysis gate, the jaxpr audit) initialises no backend — one process
  per chip;
- without a TPU the script refuses: non-zero exit, a one-line reason, no
  result line; the same in a directory that holds nothing of the repo;
- each phase's pass/fail predicate on canned ``/device`` ``/health``
  ``/metrics`` documents: native-only dispatch → fail, tripped breaker →
  fail, a cold compile in the cache child → fail;
- a device program forced to fail (monkeypatched program, no new switch)
  fails phase ``air4`` by name even though the host loop answered.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

SIZES = {"block_txs": 1000, "blocks": 5, "senders": 64, "full_width": 10_000}
TPU = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}


def _clean_env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items() if not k.startswith("FISCO_")}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["PYTHONPATH"] = REPO
    env.update(extra)
    return env


# -- one process per chip -----------------------------------------------------


def test_parent_imports_neither_jax_nor_the_package():
    code = (
        "import sys, chip_smoke\n"
        "chip_smoke.Smoke(rehearse=False)\n"
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith(('jax.', "
        "'jaxlib', 'fisco_bcos_tpu'))]\n"
        "assert not bad, bad\n"
    )
    res = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=_clean_env(),
        capture_output=True, text=True, timeout=120,
    )
    assert res.returncode == 0, res.stderr


def test_package_import_and_static_gates_initialise_no_backend():
    """What a parent may do before it spawns a chip child: import the
    package, run the static-analysis gate and the one-program jaxpr audit.
    All of it must leave JAX without a backend, or the parent would hold
    the chip."""
    code = (
        "import fisco_bcos_tpu\n"
        "from fisco_bcos_tpu.analysis import check_repo, progaudit\n"
        "check_repo()\n"
        "progaudit.audit(programs=["
        "'fisco_bcos_tpu/ops/keccak.py:keccak256_blocks'])\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n"
    )
    res = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO,
        env=_clean_env(JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600,
    )
    assert res.returncode == 0, res.stderr[-2000:]


# -- refusal --------------------------------------------------------------------


def test_refuses_without_a_tpu():
    """Children are pinned to ``tpu`` whatever the caller's environment says,
    so on a CPU-only machine the first child fails to initialise and the
    script stops with the reason — it never drops to the CPU."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env=_clean_env(JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert res.returncode != 0
    lines = [ln for ln in res.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, res.stdout
    assert "no tpu backend" in lines[0] and "refusing" in lines[0]
    assert '"ok"' not in res.stdout


def test_refuses_without_the_repository(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    res = subprocess.run(
        [sys.executable, str(lone)], cwd=tmp_path, env=_clean_env(),
        capture_output=True, text=True, timeout=60,
    )
    assert res.returncode != 0
    assert "fisco_bcos_tpu/ is not next to this script" in res.stdout
    assert '"ok"' not in res.stdout


def test_rehearsal_can_never_print_a_result(monkeypatch, capsys):
    smoke = chip_smoke.Smoke(rehearse=True)
    monkeypatch.setattr(smoke, "preflight", lambda: True)
    smoke.ident = {"platform": "cpu", "device_kind": "cpu", "count": 1}
    rc = smoke.run([])
    out = capsys.readouterr().out
    assert rc != 0 and '"ok"' not in out
    assert all("REHEARSAL" in ln for ln in out.splitlines() if ln.strip())


# -- predicates on canned documents ------------------------------------------------

METRICS_DEVICE = """\
# HELP fisco_device_dispatch_path_total batch dispatches split by chosen leg
# TYPE fisco_device_dispatch_path_total counter
fisco_device_dispatch_path_total{op="admission",path="device"} 23.0
fisco_device_dispatch_path_total{op="admission",path="native"} 4.0
fisco_device_dispatch_path_total{op="secp256k1_verify",path="native"} 60.0
"""
METRICS_NATIVE_ONLY = """\
fisco_device_dispatch_path_total{op="admission",path="native"} 27.0
fisco_device_dispatch_path_total{op="secp256k1_verify",path="native"} 60.0
"""


def _ledger_row(op, shape, cold, hits, ms):
    return {
        "op": op, "shape": shape, "cold_compiles": cold, "cache_hits": hits,
        "compile_ms": ms, "lowering_ms": 0.0, "retrieval_ms": 0.0,
        "last_source": "cold" if cold else "persistent_cache", "t_last": 1.0,
    }


def _device_doc(cold=2, hits=0, failures=None, max_native=8):
    return {
        "enabled": True,
        "device": dict(TPU),
        "failures": failures or {},
        "max_batch": {"admission": 10_000, "admission_native": max_native},
        "ledger": [
            _ledger_row("admission", "(1024, 2)", cold and 1, hits and 1, 180e3),
            _ledger_row("admission", "(10240, 1)", cold and 1, hits and 1, 200e3),
            _ledger_row("keccak256", "2048", 0, 1, 900.0),
        ],
        "compile_counts": {"admission": 2, "keccak256": 3, "merkle_root": 1},
        "totals": {
            "cold_compiles": cold, "cache_hits": hits + 1, "compile_ms": 380.9e3,
        },
    }


def _health(**degraded):
    comps = {
        "device-plane": {
            "status": "ok", "critical": True, "for_seconds": 9.0,
            "reason": "coalescing scheduler up on platform=tpu "
            "device_kind=TPU v5 lite count=1",
        },
        "proof-plane": {"status": "ok", "reason": "", "critical": True},
    }
    for name, reason in degraded.items():
        comps[name.replace("_", "-")] = {
            "status": "degraded", "reason": reason, "critical": False,
        }
    return {"status": "degraded" if degraded else "ok", "components": comps}


def _air4_doc():
    return {
        "device": dict(TPU), "blocks": 5, "cutover": 256,
        "submitted": 5000, "committed": 5000, "rejected": 0,
        "heights": [5, 5, 5, 5], "state_roots": ["ab" * 32] * 4,
        "first_batch": {
            "lanes": 1000, "mismatch_lanes": [], "invalid_lanes": [],
            "expected_invalid": [],
        },
        "corrupted": {
            "lanes": 1000, "mismatch_lanes": [], "invalid_lanes": [3, 7, 500, 999],
            "expected_invalid": [3, 7, 500, 999],
        },
        "full_width": {
            "lanes": 10_000, "mismatch_lanes": [], "invalid_lanes": [],
            "expected_invalid": [], "bucket": 10_240, "op": "admission",
        },
        "metrics_text": METRICS_DEVICE,
        "device_doc": _device_doc(),
        "health": _health(),
        "breaker_state": "closed",
        "compiles_after_first_block": 0, "compiled_in_window": {},
    }


def test_air4_good_document_passes():
    assert chip_smoke.check_air4(_air4_doc(), "tpu", SIZES) == []


def test_air4_on_the_wrong_platform_fails():
    doc = _air4_doc()
    doc["device"]["platform"] = "cpu"
    why = chip_smoke.check_air4(doc, "tpu", SIZES)
    assert any("platform 'cpu'" in w for w in why)


def test_air4_native_only_dispatch_fails():
    doc = _air4_doc()
    doc["metrics_text"] = METRICS_NATIVE_ONLY
    doc["device_doc"]["max_batch"]["admission_native"] = 1000
    why = chip_smoke.check_air4(doc, "tpu", SIZES)
    assert any("went native" in w for w in why)
    assert any("cutover 256" in w for w in why)


def test_air4_too_few_device_dispatches_fails():
    doc = _air4_doc()
    doc["metrics_text"] = METRICS_DEVICE.replace("23.0", "3.0")
    why = chip_smoke.check_air4(doc, "tpu", SIZES)
    assert why == ["3 device admission dispatches for 5 blocks"]


def test_air4_tripped_breaker_fails():
    doc = _air4_doc()
    doc["breaker_state"] = "open"
    doc["health"] = _health(device_crypto="XlaRuntimeError: INTERNAL")
    doc["device_doc"] = _device_doc(
        failures={"admission": {"count": 2, "last_error": "XlaRuntimeError: x"}}
    )
    doc["metrics_text"] = (
        METRICS_DEVICE
        + 'fisco_device_dispatch_path_total{op="admission",path="host_fallback"} 9.0\n'
    )
    why = "\n".join(chip_smoke.check_air4(doc, "tpu", SIZES))
    assert "device program admission failed 2x" in why
    assert "/health row device-crypto is degraded" in why
    assert "breaker is open" in why
    assert "host fallback" in why


def test_air4_diverged_or_short_chain_fails():
    doc = _air4_doc()
    doc["committed"] = 4000
    doc["heights"] = [4, 4, 4, 3]
    doc["state_roots"] = ["ab" * 32] * 3 + ["cd" * 32]
    why = "\n".join(chip_smoke.check_air4(doc, "tpu", SIZES))
    assert "committed 4000 of 5000" in why
    assert "replicas at heights" in why and "state root" in why


def test_air4_lane_mismatches_fail():
    doc = _air4_doc()
    doc["first_batch"]["mismatch_lanes"] = [17]
    doc["corrupted"]["invalid_lanes"] = [3, 7, 500]  # one corrupt lane accepted
    doc["full_width"]["mismatch_lanes"] = [1, 2, 3]
    why = "\n".join(chip_smoke.check_air4(doc, "tpu", SIZES))
    assert "first batch: 1 lanes differ" in why
    assert "corrupted batch: validity bits lowered at [3, 7, 500]" in why
    assert "full-width block: 3 lanes differ" in why


def test_air4_compiles_inside_the_window_fail():
    doc = _air4_doc()
    doc["compiles_after_first_block"] = 1
    doc["compiled_in_window"] = {"keccak2564096": 1}
    assert any("driven window" in w for w in chip_smoke.check_air4(doc, "tpu", SIZES))


def test_air4_full_width_op_follows_the_device_count():
    doc = _air4_doc()
    doc["device"]["count"] = 4  # four chips visible: the block must shard
    assert any(
        "admission_sharded" in w for w in chip_smoke.check_air4(doc, "tpu", SIZES)
    )
    doc["full_width"]["op"] = "admission_sharded"
    assert chip_smoke.check_air4(doc, "tpu", SIZES) == []


def test_cache_child_cold_compile_fails():
    doc = _air4_doc()
    doc["device_doc"] = _device_doc(cold=0, hits=2)
    assert chip_smoke.check_cache(doc) == []
    doc["device_doc"] = _device_doc(cold=2, hits=0)
    why = chip_smoke.check_cache(doc)
    assert len(why) == 1 and "2 cold compiles in the cache child" in why[0]
    assert "admission(1024, 2)" in why[0]


def _air_doc():
    return {
        "client": {
            "sent": 300, "acknowledged": 300, "read_back": 300, "bad_status": 0,
            "block_number": 4,
        },
        "device_doc": _device_doc(),
        "health": _health(),
        "clean_sigterm": True, "sigterm_detail": "exited after SIGTERM",
    }


def test_air_predicate():
    assert chip_smoke.check_air(_air_doc(), "tpu") == []
    doc = _air_doc()
    doc["client"]["read_back"] = 299
    doc["health"] = _health(device_recompile="recompile storm")
    doc["clean_sigterm"] = False
    doc["device_doc"]["compile_counts"].pop("merkle_root")
    why = "\n".join(chip_smoke.check_air(doc, "tpu"))
    assert "299/300 acknowledged txs read back" in why
    assert "device-recompile is degraded" in why
    assert "no clean SIGTERM" in why
    assert "no merkle_root program dispatched" in why
    cpu = copy.deepcopy(_air_doc())
    cpu["device_doc"]["device"]["platform"] = "cpu"
    assert any("platform 'cpu'" in w for w in chip_smoke.check_air(cpu, "tpu"))


# -- a device program forced to fail ---------------------------------------------


def test_forced_device_program_failure_fails_air4_by_name(monkeypatch, tmp_path):
    """The seam is the program itself: patch the jitted admission step to
    raise. The host loop answers every batch (the product's resilience), the
    chain commits — and phase air4 still fails, naming the program."""
    from fisco_bcos_tpu.crypto import admission
    from fisco_bcos_tpu.device import dispatch as dispatch_mod
    from fisco_bcos_tpu.observability.device import LEDGER
    from fisco_bcos_tpu.resilience import CircuitBreaker
    from fisco_bcos_tpu.resilience.breaker import HealthRegistry

    def boom(*_a, **_k):
        raise RuntimeError("forced device failure")

    monkeypatch.setattr(admission, "admission_step_packed", boom)
    monkeypatch.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")
    # a private breaker + registry: the process-wide ones must not trip
    monkeypatch.setattr(
        dispatch_mod, "_DEVICE_BREAKER",
        CircuitBreaker("device-crypto", failure_threshold=2, reset_timeout=60.0,
                       critical=False, registry=HealthRegistry()),
    )
    out = tmp_path / "air4.json"
    args = argparse.Namespace(
        out=str(out), blocks=2, block_txs=8, senders=4, full_width=8, stall=60.0,
    )
    try:
        assert chip_smoke.child_air4(args) == 0
        doc = json.loads(out.read_text())
    finally:
        LEDGER.reset()
    # the host loop kept every answer right …
    assert doc["committed"] == doc["submitted"] == 16
    assert doc["first_batch"]["mismatch_lanes"] == []
    # … and the smoke still refuses to call it a pass, by name
    sizes = {"block_txs": 8, "blocks": 2, "senders": 4, "full_width": 8}
    why = "\n".join(chip_smoke.check_air4(doc, "cpu", sizes))
    assert "device program admission failed" in why
    assert "forced device failure" in why
    assert "breaker is open" in why


def test_parse_metric_reads_labelled_samples():
    got = chip_smoke.parse_metric(METRICS_DEVICE, "fisco_device_dispatch_path_total")
    assert got[(("op", "admission"), ("path", "device"))] == 23.0
    assert chip_smoke.dispatch_paths(METRICS_DEVICE, "admission") == {
        "device": 23.0, "native": 4.0,
    }
    assert np.isclose(sum(got.values()), 87.0)

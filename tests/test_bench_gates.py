"""bench.py's honesty gates: a child off the chip measures nothing, a child
whose own correctness gate fails prints no value and exits non-zero, and
every metric line names the device it ran on."""

from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402
from fisco_bcos_tpu.utils import jaxenv  # noqa: E402


def test_child_off_the_chip_exits_before_measuring(monkeypatch, capsys):
    monkeypatch.setattr(
        jaxenv, "device_identity",
        lambda: {"platform": "cpu", "device_kind": "cpu", "count": 1},
    )
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)  # JAX fell back by itself
    monkeypatch.setattr(bench, "_DEVICE", {})
    with pytest.raises(SystemExit) as exc:
        bench._main_only("merkle")
    assert exc.value.code == bench.RC_NOT_ON_CHIP
    out = capsys.readouterr().out
    assert "bench refused" in out and '"metric"' not in out


def test_explicit_cpu_is_allowed_and_stamped(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # the caller's own choice
    monkeypatch.setattr(bench, "_DEVICE", {})
    bench._init_jax()
    bench._emit("some_metric", 1.0, "unit", 1.0)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["platform"] == "cpu" and line["device_count"] >= 1
    assert "device_kind" in line


def test_failed_correctness_gate_exits_nonzero_without_a_value(monkeypatch, capsys):
    def gate_fails():
        raise bench.GateFailure("device admission rejected valid signatures")

    monkeypatch.setattr(bench, "bench_admission", gate_fails)
    monkeypatch.setattr(bench, "_init_jax", lambda: None)
    with pytest.raises(SystemExit) as exc:
        bench._main_only("admission")
    assert exc.value.code == 1
    out = capsys.readouterr().out
    assert "rejected valid signatures" in out and '"metric"' not in out

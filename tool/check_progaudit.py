#!/usr/bin/env python
"""Program-auditor smoke check (ISSUE 20 acceptance):

- ``python -m fisco_bcos_tpu.analysis --jaxpr`` exits 0 over the repo:
  every non-slow program re-traces to its committed fingerprint and the
  baseline covers the FULL inventory with no stale keys;
- the new checkers (host-sync, dtype-drift, program-coherence) FIRE over
  their violation fixtures;
- fingerprints are deterministic ACROSS PROCESSES: two subprocess audits
  of the same program agree digest-for-digest (the canonicalizer admits
  no id()/ordering leakage);
- the stale-key guard actually guards: a baseline with a ghost program
  fails the diff naming the ghost;
- ``--fusion-report`` is non-empty and names the fused-admission chain.

Runs under ``JAX_PLATFORMS=cpu``; the ``--jaxpr`` leg re-traces every
non-slow program (~minutes, the secp/sm2/ed25519 traces dominate)::

    python tool/check_progaudit.py [--fast]

``--fast`` audits the sub-second programs only (coverage/stale checks
still run against the full inventory). Exit 0 on success, 1 with a named
failure otherwise.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

FAST_SUBSET = (
    "fisco_bcos_tpu/ops/keccak.py:keccak256_blocks,"
    "fisco_bcos_tpu/ops/sha256.py:sha256_blocks,"
    "fisco_bcos_tpu/ops/sm3.py:sm3_blocks,"
    "fisco_bcos_tpu/ops/address.py:sender_address_device,"
    "fisco_bcos_tpu/ops/merkle.py:_device_root_fn.run,"
    "fisco_bcos_tpu/ops/merkle.py:_device_tree_fn.tree"
)


def fail(name: str, detail: str = "") -> None:
    print(f"FAIL {name}: {detail}")
    raise SystemExit(1)


def ok(name: str, detail: str = "") -> None:
    print(f"ok   {name}" + (f": {detail}" if detail else ""))


def _run(args: list[str], timeout: int = 1800):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True,
        cwd=REPO, env=env, timeout=timeout,
    )


def main() -> int:
    fast = "--fast" in sys.argv[1:]

    # 1. the repo audits clean against the committed baseline
    audit_args = ["-m", "fisco_bcos_tpu.analysis", "--jaxpr"]
    if fast:
        audit_args += ["--jaxpr-programs", FAST_SUBSET]
    proc = _run(audit_args)
    if proc.returncode != 0:
        fail(
            "repo-jaxpr-clean",
            f"--jaxpr exited {proc.returncode}:\n"
            f"{proc.stdout[-2000:]}{proc.stderr[-1000:]}",
        )
    ok("repo-jaxpr-clean", proc.stdout.strip().splitlines()[-1])

    # 2. the new checkers fire over their fixtures
    from fisco_bcos_tpu.analysis import run_all

    fixtures = os.path.join(REPO, "tests", "fixtures", "analysis")
    keys = {f.key for f in run_all(fixtures)}
    for want in (
        "host-sync:tests/fixtures/analysis/bad_host_sync.py:wrapper:"
        "asarray-out",
        "dtype-drift:tests/fixtures/analysis/bad_dtype_drift.py:drifty:"
        "x64-float64",
        "program-coherence:tests/fixtures/analysis/bad_coherence.py:"
        "orphan:missing-spec-orphan",
        "program-coherence:tests/fixtures/analysis/bad_coherence.py:"
        ":pad-off-ladder-100",
    ):
        if want not in keys:
            fail("fixtures-fire", f"expected finding absent: {want}")
    ok("fixtures-fire", "host-sync, dtype-drift, program-coherence")

    # 3. cross-process fingerprint determinism (one cheap program, two
    # fresh interpreters — catches id()/hash-seed leakage that a
    # same-process double trace cannot)
    snippet = (
        "import json\n"
        "from fisco_bcos_tpu.analysis import progaudit\n"
        "r = progaudit.audit("
        "programs=['fisco_bcos_tpu/ops/keccak.py:keccak256_blocks'])\n"
        "e = r['programs']"
        "['fisco_bcos_tpu/ops/keccak.py:keccak256_blocks']\n"
        "print(json.dumps(e, sort_keys=True))\n"
    )
    runs = [_run(["-c", snippet], timeout=600) for _ in range(2)]
    for r in runs:
        if r.returncode != 0:
            fail("fingerprint-determinism", r.stderr[-1000:])
    e1, e2 = (json.loads(r.stdout.strip().splitlines()[-1]) for r in runs)
    if e1 != e2:
        fail(
            "fingerprint-determinism",
            f"two processes disagree: {e1['fingerprint']} vs "
            f"{e2['fingerprint']}",
        )
    ok("fingerprint-determinism", e1["fingerprint"])

    # 4. the stale-key guard names ghosts
    from fisco_bcos_tpu.analysis.progaudit import (
        diff_audit,
        load_jaxpr_baseline,
    )

    baseline = load_jaxpr_baseline()
    ghost = "fisco_bcos_tpu/ops/ghost.py:deleted_program"
    tampered = {
        "programs": dict(
            baseline.get("programs", {}),
            **{ghost: {"fingerprint": "dead", "bucket": 256}},
        )
    }
    result = {
        "programs": {},
        "failures": [],
        "missing_spec": [],
        "inventory": sorted(
            k for k in tampered["programs"] if k != ghost
        ),
        "not_traced": [],
    }
    diff = diff_audit(result, tampered)
    if diff["ok"] or ghost not in diff["stale"]:
        fail("stale-key-guard", f"ghost not flagged: {diff['stale']}")
    ok("stale-key-guard", ghost)

    # 5. the fusion report ranks the admission chain
    proc = _run(
        ["-m", "fisco_bcos_tpu.analysis", "--fusion-report",
         "--format=json"]
    )
    if proc.returncode != 0:
        fail("fusion-report", f"exited {proc.returncode}: {proc.stderr[-500:]}")
    report = json.loads(proc.stdout)
    if not report["pairs"]:
        fail("fusion-report", "no rankable pairs")
    chain = report["admission_chain"]
    if chain["ops"] != [
        "keccak256", "secp256k1_recover", "secp256k1_verify", "dedup_key"
    ]:
        fail("fusion-report", f"unexpected chain: {chain['ops']}")
    if len(chain["edges"]) != 3 or chain["predicted_saved_bytes"] <= 0:
        fail("fusion-report", f"chain not fully ranked: {chain}")
    ok(
        "fusion-report",
        f"{len(report['pairs'])} pair(s), chain saves "
        f"~{chain['predicted_saved_bytes']} B/round",
    )

    print("check_progaudit: all green")
    return 0


if __name__ == "__main__":
    sys.exit(main())

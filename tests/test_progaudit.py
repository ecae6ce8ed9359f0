"""Program auditor (ISSUE 20): jaxpr fingerprints, static costs, the
committed baseline's coverage of the jit inventory, and the fusion-edge
report.

What the suite pins:

- **zero-compile proof** — an audit is ``jax.make_jaxpr`` over
  ``ShapeDtypeStruct`` avals: after auditing real repo programs the
  compile ledger holds ZERO entries (no cold compiles, no dispatch rows).
- **fingerprint stability** — same program traced twice → identical
  digest; textually different variable names → identical digest
  (canonical renumbering); changed shape or primitive → different digest
  AND a per-primitive ``explain_change`` explanation.
- **baseline coverage by name** — every ``file:qualname`` in the jitmap
  inventory appears in ``tool/jaxpr_baseline.json`` (slow programs
  included: they are fingerprinted at update time), and no baseline key
  outlives its program (stale guard).
- **fusion report** — from the committed baseline alone, the admission
  chain keccak → recover → verify → dedup ranks among the top pairs with
  non-zero predicted saved transfer bytes.

Everything here runs under ``JAX_PLATFORMS=cpu`` and traces only the
sub-second programs; the BLS pairing programs are verified by coverage,
never re-traced (minutes-class)."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from fisco_bcos_tpu.analysis import progaudit
from fisco_bcos_tpu.analysis.progaudit.costmodel import cost
from fisco_bcos_tpu.analysis.progaudit.fingerprint import (
    explain_change,
    fingerprint,
)
from fisco_bcos_tpu.observability.device import LEDGER

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_PATH = os.path.join(REPO, "tool", "jaxpr_baseline.json")

# sub-second traces only — the audit-vs-baseline tests stay cheap
FAST_PROGRAMS = [
    "fisco_bcos_tpu/ops/keccak.py:keccak256_blocks",
    "fisco_bcos_tpu/ops/sha256.py:sha256_blocks",
    "fisco_bcos_tpu/ops/address.py:sender_address_device",
]


# -- fingerprint canonicalization --------------------------------------------


def _fp(fn, *avals):
    return fingerprint(jax.make_jaxpr(fn)(*avals))


def _aval(shape, dtype="float32"):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_fingerprint_deterministic_for_same_program():
    def f(x):
        return jnp.sum(x * 2.0 + 1.0)

    d1, s1 = _fp(f, _aval((8, 8)))
    d2, s2 = _fp(f, _aval((8, 8)))
    assert d1 == d2
    assert s1 == s2


def test_fingerprint_invariant_under_variable_renaming():
    # same computation, different python variable/argument names: the
    # canonicalizer renumbers jaxpr vars in first-appearance order, so
    # the digests must collide
    def f(x):
        tmp = x * 3.0
        return tmp + tmp

    def g(different_name):
        completely_other = different_name * 3.0
        return completely_other + completely_other

    assert _fp(f, _aval((4,)))[0] == _fp(g, _aval((4,)))[0]


def test_fingerprint_changes_with_shape():
    def f(x):
        return x * 2.0

    assert _fp(f, _aval((4,)))[0] != _fp(f, _aval((8,)))[0]


def test_fingerprint_changes_with_primitive_and_explains():
    def f(x):
        return jnp.sum(x)

    def g(x):
        return jnp.max(x)

    (df, sf), (dg, sg) = _fp(f, _aval((16,))), _fp(g, _aval((16,)))
    assert df != dg
    old = {"fingerprint": df, **sf}
    new = {"fingerprint": dg, **sg}
    explanation = explain_change(old, new)
    # the explanation names the primitive-level delta, not just "changed"
    assert "reduce_sum" in explanation or "reduce_max" in explanation, (
        explanation
    )


def test_fingerprint_changes_with_literal_value():
    def f(x):
        return x * 2.0

    def g(x):
        return x * 3.0

    assert _fp(f, _aval((4,)))[0] != _fp(g, _aval((4,)))[0]


def test_fingerprint_recurses_into_pjit_params():
    # a jitted callee folds into the caller's fingerprint through the
    # pjit eqn's jaxpr param — renaming the CALLEE must not matter either
    @jax.jit
    def inner_a(x):
        return x + 1.0

    @jax.jit
    def inner_b(y):
        return y + 1.0

    def f(x):
        return inner_a(x) * 2.0

    def g(x):
        return inner_b(x) * 2.0

    assert _fp(f, _aval((4,)))[0] == _fp(g, _aval((4,)))[0]


# -- cost model ---------------------------------------------------------------


def test_cost_model_counts_dot_and_bytes():
    def f(a, b):
        return jnp.dot(a, b)

    c = cost(jax.make_jaxpr(f)(_aval((8, 16)), _aval((16, 4))))
    assert c["flops"] == 2 * 16 * 8 * 4
    assert c["bytes_in"] == (8 * 16 + 16 * 4) * 4
    assert c["bytes_out"] == 8 * 4 * 4


def test_cost_model_free_ops_cost_nothing():
    def f(x):
        return jnp.reshape(x, (4, 2)).T

    c = cost(jax.make_jaxpr(f)(_aval((8,))))
    assert c["flops"] == 0


# -- auditing real repo programs ---------------------------------------------


def test_audit_never_compiles():
    """The zero-compile proof: abstract eval only — after auditing a real
    device program the compile ledger has no cold compiles, no dispatch
    rows, nothing."""
    LEDGER.reset()
    result = progaudit.audit(programs=[FAST_PROGRAMS[0]])
    assert FAST_PROGRAMS[0] in result["programs"]
    assert not result["failures"]
    assert LEDGER.cold_compile_count() == 0
    assert LEDGER.snapshot() == []


@pytest.mark.skipif(
    not os.path.exists(BASELINE_PATH), reason="baseline not generated yet"
)
def test_fast_subset_matches_committed_baseline():
    """Re-trace the cheap programs and diff against the committed
    baseline: no new, no changed. (Coverage/stale run against the FULL
    inventory even on a subset audit — exercised separately below.)"""
    result = progaudit.audit(programs=list(FAST_PROGRAMS))
    baseline = progaudit.load_jaxpr_baseline()
    diff = progaudit.diff_audit(result, baseline)
    assert not diff["new"], diff["new"]
    assert not diff["changed"], diff["changed"]
    assert not diff["failures"], diff["failures"]
    assert not diff["missing_spec"], diff["missing_spec"]


# PR 45 gave ops/sm3 a second form of a compression for the fused SM3 merkle
# tree (`_compress_rolling`, beside `_compress`, sharing `_round`): the
# programs that hash through the first must be the ones the baseline holds
# (how `sm3_blocks` is traced moves the SM admission program by tens of
# milliseconds a block on the chip: ops/sm3.sm3_fixed), and the tree program
# the one that was measured there.
@pytest.mark.parametrize("key", [
    "fisco_bcos_tpu/ops/sm3.py:sm3_blocks",
    "fisco_bcos_tpu/ops/merkle.py:_device_root_fn.run",
    "fisco_bcos_tpu/ops/merkle.py:_device_tree_fn.tree",
])
def test_the_sm3_and_merkle_programs_are_the_baselines(key):
    result = progaudit.audit(programs=[key])
    assert not result["failures"], result["failures"]
    diff = progaudit.diff_audit(result, progaudit.load_jaxpr_baseline())
    assert not diff["new"] and not diff["changed"], diff


def test_sm3_blocks_round_scan_is_not_unrolled():
    """The admission program's guard, said structurally: `sm3_blocks` runs
    `_schedule`'s pass and then 64 rounds as a scan of 64 steps over the
    schedule's rows, one round a step; the unrolled, rolling form is the
    merkle tree's alone."""
    from fisco_bcos_tpu.ops import sm3

    jaxpr = jax.make_jaxpr(sm3.sm3_blocks)(
        _aval((256, 2, 16), "uint32"), _aval((256,), "int32"))

    def scans(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "scan":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scans(sub)

    rounds = [e for e in scans(jaxpr.jaxpr) if e.params["length"] == 64]
    assert len(rounds) == 1
    (eqn,) = rounds
    assert eqn.params["unroll"] == 1
    assert eqn.params["num_carry"] == 8  # the registers; the message is xs


def test_two_hashes_of_one_trace_keep_a_step_function_each():
    """What the fingerprints above cannot see. A program that hashes more
    than once (the SM admission program: five times) lowers each
    `_compress`'s round scan to a step function of its own, because
    `_compress` hands `lax.scan` a function made in that call. With one
    module-level step JAX shares the body between hashes of one lane shape:
    the same arithmetic, but another module text, so another compile-cache
    key (the 1,024-lane SM program compiled again, 242 s of set-up, on the
    chip in PR 45) and a program nobody has timed (PR 26: how these hashes
    are traced moved the SM block by 28 ms)."""
    import re

    from fisco_bcos_tpu.ops import sm3

    text = jax.jit(lambda a, b: (sm3.sm3_fixed(a), sm3.sm3_fixed(b))).lower(
        _aval((8, 1, 16), "uint32"), _aval((8, 2, 16), "uint32")).as_text()
    # two `sm3_blocks` (one a block count), each with its absorb step and its
    # round step
    assert len(re.findall(r"func\.func private @sm3_blocks", text)) == 2
    assert len(re.findall(r"func\.func private @closed_call", text)) == 4


@pytest.mark.skipif(
    not os.path.exists(BASELINE_PATH), reason="baseline not generated yet"
)
def test_baseline_covers_full_inventory_by_name():
    """Every inventoried program — slow BLS pairings included — has a
    committed fingerprint (or a skip reason), and no baseline key
    outlives its program. Pure name check: nothing is traced."""
    inv = progaudit.inventory_keys()
    with open(BASELINE_PATH, encoding="utf-8") as f:
        base = json.load(f)["programs"]
    missing = sorted(set(inv) - set(base))
    stale = sorted(set(base) - set(inv))
    assert not missing, f"programs without committed fingerprints: {missing}"
    assert not stale, f"baseline keys whose program is gone: {stale}"
    # traced entries carry the full static record; skipped ones a reason
    for key, entry in base.items():
        if "skip" in entry:
            assert entry["skip"], key
        else:
            for field in (
                "fingerprint", "bucket", "eqns", "primitives", "dtypes",
                "flops", "bytes_in", "bytes_out", "bytes_intermediate",
            ):
                assert field in entry, f"{key} missing {field}"


def test_diff_flags_stale_and_missing_on_subset_audit():
    """The stale-key guard works even when only one program is traced:
    inventory is always the full universe."""
    result = progaudit.audit(programs=[FAST_PROGRAMS[0]])
    fake = {
        "programs": {
            FAST_PROGRAMS[0]: dict(result["programs"][FAST_PROGRAMS[0]]),
            "fisco_bcos_tpu/ops/ghost.py:deleted_program": {
                "fingerprint": "dead", "bucket": 256,
            },
        }
    }
    diff = progaudit.diff_audit(result, fake)
    assert diff["stale"] == [
        "fisco_bcos_tpu/ops/ghost.py:deleted_program"
    ]
    # everything in the real inventory except the one traced program is
    # missing from the fake baseline — coverage gaps fail the diff
    assert len(diff["missing"]) == len(result["inventory"]) - 1
    assert not diff["ok"]


def test_diff_explains_fingerprint_change():
    result = progaudit.audit(programs=[FAST_PROGRAMS[0]])
    entry = dict(result["programs"][FAST_PROGRAMS[0]])
    tampered = dict(entry)
    tampered["fingerprint"] = "0" * 16
    tampered["eqns"] = entry["eqns"] + 7
    diff = progaudit.diff_audit(
        result, {"programs": {FAST_PROGRAMS[0]: tampered}}
    )
    (changed,) = [
        c for c in diff["changed"] if c["key"] == FAST_PROGRAMS[0]
    ]
    assert "eqns" in changed["explanation"]


# -- fusion report ------------------------------------------------------------


@pytest.mark.skipif(
    not os.path.exists(BASELINE_PATH), reason="baseline not generated yet"
)
def test_fusion_report_ranks_admission_chain():
    """ISSUE 20 acceptance: from the committed baseline alone the fused
    admission chain's edges appear among the top-ranked mergeable pairs
    with non-zero predicted transfer savings."""
    baseline = progaudit.load_jaxpr_baseline()
    report = progaudit.fusion_report(baseline, top=10)
    chain = report["admission_chain"]
    assert list(chain["ops"]) == list(progaudit.ADMISSION_CHAIN)
    assert chain["predicted_saved_bytes"] > 0
    assert chain["dispatches_collapsed"] == 3
    top_pairs = {(r["producer"], r["consumer"]) for r in report["pairs"]}
    for a, b in zip(chain["ops"], chain["ops"][1:]):
        assert (a, b) in top_pairs, (a, b, sorted(top_pairs))
    for r in report["pairs"]:
        assert r["predicted_saved_bytes"] >= 0
        assert r["source"] in (
            "static-chain", "measured", "static-chain+measured"
        )


@pytest.mark.skipif(
    not os.path.exists(BASELINE_PATH), reason="baseline not generated yet"
)
def test_fusion_report_weights_measured_adjacency():
    baseline = progaudit.load_jaxpr_baseline()
    unweighted = progaudit.fusion_report(baseline)
    weighted = progaudit.fusion_report(
        baseline, adjacency={"keccak256->secp256k1_recover": 500}
    )

    def saved(report):
        for r in report["pairs"]:
            if (r["producer"], r["consumer"]) == (
                "keccak256", "secp256k1_recover"
            ):
                return r["predicted_saved_bytes"], r["source"]
        raise AssertionError("chain edge absent")

    s0, src0 = saved(unweighted)
    s1, src1 = saved(weighted)
    assert s1 > s0
    assert src0 == "static-chain"
    assert src1 == "static-chain+measured"


# -- dispatch adjacency ledger ------------------------------------------------


def test_adjacency_ledger_counts_ordered_pairs():
    LEDGER.reset()
    try:
        for op in ("keccak256", "secp256k1_recover", "secp256k1_verify",
                   "keccak256", "secp256k1_recover"):
            LEDGER.note_adjacency(op)
        adj = LEDGER.adjacency()
        assert adj["keccak256->secp256k1_recover"] == 2
        assert adj["secp256k1_recover->secp256k1_verify"] == 1
        assert adj["secp256k1_verify->keccak256"] == 1
    finally:
        LEDGER.reset()
    assert LEDGER.adjacency() == {}

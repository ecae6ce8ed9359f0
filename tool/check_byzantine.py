#!/usr/bin/env python
"""Byzantine chaos-lab smoke check (ISSUE 15 acceptance shape, small scale).

One live 4-node committee with one seed-deterministic adversary inside it,
runnable locally and from CI next to the other check_* tools:

1. **Catalog** — every cataloged attack (equivocation, stale-view replay,
   vote conflict, fabricated prepared-cert, forged QC vote) is *detected*:
   its evidence kinds count into ``fisco_consensus_evidence_total{kind}``
   and land on the EVIDENCE board.
2. **Demotion** — the adversary's validator source is demoted through the
   existing strike/quota board (the same ``SOURCE_DEMOTED`` treatment tx
   spammers get), and demotion costs only the QC fast path: the honest
   committee keeps committing (liveness asserted as real block progress
   during the attack run).
3. **Safety** — the cross-node chain auditor reports zero violations:
   agreement on the committed hash per height, no gaps/double-commits,
   a quorum-valid certificate on every committed header.
4. **Passthrough** — with no adversary driving attacks, a clean flood of
   the same shape raises zero evidence (byzantine-off is a no-op).

Exit 0 on success, 1 with a named failure otherwise::

    python tool/check_byzantine.py
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("FISCO_DEVICE_WINDOW_MS", "0")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from fisco_bcos_tpu.utils.jaxenv import pin_cpu  # noqa: E402

# a CPU smoke: CPU platform, fast-compile XLA flags, the test suite's 32-lane
# batch bucket and the shared compile cache
pin_cpu()


def fail(msg: str) -> None:
    print(f"FAIL: {msg}")
    raise SystemExit(1)


def check_clean_passthrough() -> None:
    """A clean flood (same committee shape, no attacks) raises zero
    evidence — the byzantine layer is detection, never friction."""
    from fisco_bcos_tpu.consensus.audit import EVIDENCE, audit_chain
    from fisco_bcos_tpu.scenario import ByzantineHarness

    EVIDENCE.reset()
    h = ByzantineHarness(seed=7)
    for _ in range(3):
        if not h.commit_block(4):
            fail("clean committee failed to commit")
    if EVIDENCE.count() != 0:
        fail(f"clean flood raised evidence: {EVIDENCE.counts()}")
    audit = audit_chain(h.nodes)
    if not audit["ok"]:
        fail(f"clean-chain audit: {audit['violations']}")
    print(
        f"ok: clean passthrough — {h.height()} blocks, zero evidence, "
        f"audit clean ({audit['headers_checked']} headers)"
    )


def check_catalog_live() -> None:
    """The full attack catalog against a live committee: every attack
    detected, the adversary demoted, honest liveness held, audit green."""
    from fisco_bcos_tpu.scenario import run_byzantine_scenario

    doc = run_byzantine_scenario(seed=0, scale=0.5)
    undetected = [r["attack"] for r in doc["attacks"] if not r["detected"]]
    if undetected:
        fail(
            f"attacks not detected: {undetected} "
            f"(evidence {doc['evidence_counts']})"
        )
    if not doc["adversary_demoted"]:
        fail(
            f"adversary (index {doc['adversary_index']}) was never demoted: "
            f"{doc['quotas']}"
        )
    # liveness: the honest committee committed real blocks WHILE the
    # catalog ran (one per attack interleaved by the scenario driver)
    if doc["blocks_during_attacks"] < len(doc["attacks"]):
        fail(
            f"honest committee stalled during attacks: "
            f"{doc['blocks_during_attacks']} blocks over "
            f"{len(doc['attacks'])} attacks"
        )
    if not doc["audit"]["ok"]:
        fail(f"byzantine-run chain audit: {doc['audit']['violations']}")
    print(
        f"ok: catalog live — {len(doc['attacks'])}/{len(doc['attacks'])} "
        f"attacks detected (evidence {doc['evidence_counts']}), adversary "
        f"index {doc['adversary_index']} demoted, "
        f"{doc['blocks_during_attacks']} honest blocks during the run, "
        f"audit clean at height {doc['honest_height']}"
    )


def check_demoted_liveness() -> None:
    """Demotion must never cost quorum: after the catalog demoted the
    adversary, a committee that NEEDS its (now-valid) votes — n=4, f=1,
    one honest node isolated — still commits."""
    from fisco_bcos_tpu.scenario import ByzantineHarness
    from fisco_bcos_tpu.txpool.quota import get_quotas

    h = ByzantineHarness(seed=1)
    for _ in range(2):
        if not h.commit_block(2):
            fail("warmup commit failed")
    # demote the adversary directly through the strike board
    q = get_quotas()
    from fisco_bcos_tpu.consensus.audit import EVIDENCE_GROUP

    src = h.adversary_source()
    for _ in range(8):
        q.note_invalid(EVIDENCE_GROUP, src, 1)
    if not h.adversary_demoted():
        fail("strike board did not demote the adversary source")
    # silence one honest non-leader: quorum (3 of 4) now REQUIRES the
    # demoted member's vote — the commit below only succeeds if demotion
    # never costs quorum membership
    h.reconcile()
    number = h.height() + 1
    leader = h.leader_for(number)
    silenced = next(
        n for n in h.honest if n is not leader and n is not h.adversary.node
    )
    h.silence(silenced)
    try:
        if not h.commit_block(2):
            fail("quorum that needs the demoted member's vote failed")
        if h.height() < number:
            fail("no progress after demotion")
    finally:
        h.rejoin(silenced)
    h.reconcile()
    if len({n.block_number() for n in h.nodes}) != 1:
        fail("silenced node did not converge after rejoining")
    print(
        f"ok: demoted-member liveness — chain advanced to {h.height()} "
        f"with {src} in the penalty box and one honest node silenced"
    )


def main() -> None:
    check_clean_passthrough()
    check_catalog_live()
    check_demoted_liveness()
    print("OK: byzantine chaos-lab smoke passed")


if __name__ == "__main__":
    main()

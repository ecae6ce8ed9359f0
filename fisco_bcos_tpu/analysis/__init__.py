"""Project-native static analysis + runtime lock-order recording.

The Python/JAX reproduction's answer to the reference node's C++ tooling
(TSan, clang-tidy, sanitizer CI): an AST-walking framework whose rules
encode THIS project's invariants —

- :mod:`.checkers.device_dispatch` — device crypto/hash dispatch only
  through the DevicePlane seams;
- :mod:`.checkers.shape_bucket` — jit-fed batch shapes routed through the
  bucket ladder (recompile-churn guard);
- :mod:`.checkers.jit_purity` — no side effects inside jit-traced bodies;
- :mod:`.checkers.lock_order` — static lock-acquisition graph: cycles and
  blocking IO held under a lock;
- :mod:`.checkers.guarded_state` — per-class lock-claim inference: writes
  and compound RMWs of a claimed field outside its guard, guarded mutable
  containers escaping by reference;
- :mod:`.checkers.atomicity` — lock-free check-then-act sequences and
  unlocked lazy-init of shared singletons;
- :mod:`.checkers.exceptions` — no silent broad-except swallows;
- :mod:`.checkers.contracts` — RPC idempotency classification, span
  closure, histogram bucket contract, the server-side span seam.

Findings diff against the checked-in baseline
(``tool/analysis_baseline.json``): accepted debt passes, any NEW key
fails. Run locally with ``python -m fisco_bcos_tpu.analysis``; enforced in
tier-1 by ``tests/test_static_analysis.py``.

The runtime complements: :mod:`.lockorder` — instrumented
``threading.Lock``/``RLock`` recording real per-thread acquisition chains
across the test suite, failing the session on ordering cycles or RPC IO
under a foreign lock; :mod:`.raceguard` — the sampling Eraser-lockset
recorder over the hot-class watch-list (``FISCO_RACEGUARD=1``); and
:mod:`.interleave` — the seeded deterministic interleaving explorer that
drives :mod:`.harnesses` through forced preemption schedules
(``tool/check_races.py``).

Everything importable from here is jax-free: the CLI and the tier-1 test
run on a cold interpreter in well under the 30 s budget.
"""

from __future__ import annotations

from .core import (  # noqa: F401
    DEFAULT_BASELINE,
    Checker,
    Finding,
    Source,
    diff_findings,
    load_baseline,
    load_sources,
    save_baseline,
)


def run_all(
    root: str | None = None,
    checkers=None,
    sources: list[Source] | None = None,
) -> list[Finding]:
    """Run every (or the given) checkers over the package; stable order."""
    from .checkers import ALL_CHECKERS

    srcs = sources if sources is not None else load_sources(root)
    out: list[Finding] = []
    for cls in checkers or ALL_CHECKERS:
        out.extend(cls().run(srcs))
    out.sort(key=lambda f: (f.file, f.line, f.key))
    return out


def check_repo(
    root: str | None = None, baseline_path: str | None = None
) -> tuple[list[Finding], list[str]]:
    """(new findings vs baseline, stale baseline keys) — the enforcement
    entry point shared by the CLI and the tier-1 test."""
    findings = run_all(root)
    baseline = load_baseline(baseline_path)
    return diff_findings(findings, baseline)

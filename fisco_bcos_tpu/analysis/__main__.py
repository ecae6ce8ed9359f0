"""CLI: ``python -m fisco_bcos_tpu.analysis [--format=json|text] ...``.

Exit codes: 0 = clean (no non-baselined findings, no stale baseline
entries), 1 = new findings or stale baseline entries — the same contract
the tier-1 test enforces — 2 = usage error. ``--update-baseline`` rewrites the baseline to the current finding
set (review the diff before committing it — the baseline is accepted
debt, not a mute button).
"""

from __future__ import annotations

import argparse
import json
import sys

from . import (
    DEFAULT_BASELINE,
    diff_findings,
    load_baseline,
    run_all,
    save_baseline,
)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m fisco_bcos_tpu.analysis",
        description="project-native invariant analyzers (see "
        "docs/static_analysis.md)",
    )
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--root", default=None, help="package dir to analyze")
    p.add_argument("--baseline", default=DEFAULT_BASELINE)
    p.add_argument(
        "--checker", action="append", metavar="NAME",
        help="run only this checker (repeatable / comma-separated); the "
        "baseline diff is scoped to the selected checkers' keys",
    )
    p.add_argument(
        "--list", action="store_true",
        help="print the registered checkers with one-line descriptions",
    )
    p.add_argument(
        "--list-jit", action="store_true",
        help="print the jit-program inventory (what tool/warm_cache.py "
        "pre-compiles) and exit",
    )
    p.add_argument(
        "--no-baseline", action="store_true",
        help="report every finding, ignoring accepted debt",
    )
    p.add_argument(
        "--update-baseline", action="store_true",
        help="rewrite the baseline file to the current finding set",
    )
    p.add_argument(
        "--jaxpr", action="store_true",
        help="abstract-eval the jit inventory and diff fingerprints/costs "
        "against tool/jaxpr_baseline.json (new/stale/changed/missing all "
        "fail; slow-marked programs verify by coverage only)",
    )
    p.add_argument(
        "--jaxpr-full", action="store_true",
        help="like --jaxpr but re-trace slow-marked programs too (the BLS "
        "pairing Miller loops — minutes-class)",
    )
    p.add_argument(
        "--jaxpr-programs", default=None, metavar="KEYS",
        help="comma-separated file:qualname (or bare qualname) subset to "
        "audit; coverage/stale checks still run against the full inventory",
    )
    p.add_argument(
        "--jaxpr-baseline", default=None,
        help="jaxpr baseline path (default tool/jaxpr_baseline.json)",
    )
    p.add_argument(
        "--update-jaxpr-baseline", action="store_true",
        help="re-audit the FULL inventory (slow programs included) and "
        "rewrite the jaxpr baseline — review the diff before committing",
    )
    p.add_argument(
        "--fusion-report", action="store_true",
        help="rank mergeable program pairs from the jaxpr baseline "
        "(+ measured dispatch adjacency via --adjacency)",
    )
    p.add_argument(
        "--adjacency", default=None, metavar="JSON",
        help="device artifact (a saved GET /device document) whose "
        "'adjacency' map weights the fusion report",
    )
    args = p.parse_args(argv)

    if args.update_jaxpr_baseline or args.jaxpr or args.jaxpr_full:
        return _jaxpr_main(args)
    if args.fusion_report:
        return _fusion_main(args)

    if args.list_jit:
        from . import jitmap

        progs = jitmap.inventory(args.root)
        if args.format == "json":
            print(json.dumps(progs, indent=2))
        else:
            for p_ in progs:
                names = ", ".join(p_["names"])
                print(f"{p_['file']}:{p_['line']}  {p_['qualname']}  [{names}]")
            print(f"{len(progs)} jitted program(s)")
        return 0

    from .checkers import ALL_CHECKERS, checker_by_name

    if args.list:
        width = max(len(c.name) for c in ALL_CHECKERS)
        for c in ALL_CHECKERS:
            desc = getattr(c, "description", "") or "(no description)"
            print(f"{c.name:<{width}}  {desc}")
        return 0

    selected = None
    if args.checker:
        names = [n for arg in args.checker for n in arg.split(",") if n]
        if not names:
            # an empty selection must not run ALL checkers against a
            # baseline scoped to NONE (every accepted debt would read new)
            print("--checker given but no checker names resolved")
            return 2
        selected = []
        for n in names:
            cls = checker_by_name(n)
            if cls is None:
                known = ", ".join(c.name for c in ALL_CHECKERS)
                print(f"unknown checker {n!r} (known: {known})")
                return 2
            selected.append(cls)
        if args.update_baseline:
            print("--update-baseline requires the full checker set "
                  "(a filtered run would drop every other checker's debt)")
            return 2

    findings = run_all(args.root, checkers=selected)
    if args.update_baseline:
        old_notes = load_baseline(args.baseline)
        save_baseline(findings, args.baseline, notes=old_notes)
        print(
            f"baseline updated: {len(findings)} accepted findings -> "
            f"{args.baseline}"
        )
        return 0
    if args.no_baseline:
        new, stale = findings, []
    else:
        baseline = load_baseline(args.baseline)
        if selected is not None:
            # scope the diff to the selected checkers: every other
            # checker's accepted debt would otherwise read as stale
            chosen = {c.name for c in selected}
            baseline = {
                k: v for k, v in baseline.items()
                if k.split(":", 1)[0] in chosen
            }
        new, stale = diff_findings(findings, baseline)

    if args.format == "json":
        print(
            json.dumps(
                {
                    "new": [
                        {
                            "key": f.key,
                            "file": f.file,
                            "line": f.line,
                            "checker": f.checker,
                            "message": f.message,
                        }
                        for f in new
                    ],
                    "stale_baseline": stale,
                    "total_findings": len(findings),
                },
                indent=2,
            )
        )
    else:
        for f in new:
            print(f.render())
        for key in stale:
            print(f"stale baseline entry (debt paid? remove it): {key}")
        print(
            f"{len(new)} new finding(s), {len(findings) - len(new)} "
            f"baselined, {len(stale)} stale baseline entr(ies)"
        )
    return 1 if (new or stale) else 0


def _jaxpr_main(args) -> int:
    """--jaxpr / --jaxpr-full / --update-jaxpr-baseline. Lazy progaudit
    import: these are the only CLI paths that load jax."""
    from . import progaudit

    if args.update_jaxpr_baseline:
        result = progaudit.audit(args.root, include_slow=True)
        if result["failures"] or result["missing_spec"]:
            for f in result["failures"]:
                print(f"audit failure: {f['key']}: {f['error']}")
            for k in result["missing_spec"]:
                print(f"no PROGSPEC entry for inventoried program: {k}")
            return 1
        progaudit.save_jaxpr_baseline(result, args.jaxpr_baseline)
        traced = sum(
            1 for e in result["programs"].values() if "skip" not in e
        )
        print(
            f"jaxpr baseline updated: {traced} program(s) fingerprinted, "
            f"{len(result['programs']) - traced} skipped with reasons"
        )
        return 0

    programs = None
    if args.jaxpr_programs:
        programs = [k for k in args.jaxpr_programs.split(",") if k]
    result = progaudit.audit(
        args.root, programs=programs, include_slow=args.jaxpr_full
    )
    baseline = progaudit.load_jaxpr_baseline(args.jaxpr_baseline)
    diff = progaudit.diff_audit(result, baseline)
    if args.format == "json":
        print(json.dumps(diff, indent=2))
    else:
        for key in diff["new"]:
            print(f"NEW program (baseline it): {key}")
        for key in diff["stale"]:
            print(f"stale baseline entry (program deleted?): {key}")
        for key in diff["missing"]:
            print(f"inventory program missing from baseline: {key}")
        for c in diff["changed"]:
            print(f"CHANGED {c['key']}: {c['explanation']}")
        for f in diff["failures"]:
            print(f"audit failure: {f['key']}: {f['error']}")
        for k in diff["missing_spec"]:
            print(f"no PROGSPEC entry for inventoried program: {k}")
        audited = sum(
            1 for e in result["programs"].values() if "skip" not in e
        )
        print(
            f"jaxpr audit: {audited} traced, "
            f"{len(result['not_traced'])} deferred "
            f"(slow/subset), {len(diff['changed'])} changed, "
            f"{len(diff['new'])} new, {len(diff['stale'])} stale, "
            f"{len(diff['missing'])} missing"
        )
    return 0 if diff["ok"] else 1


def _fusion_main(args) -> int:
    from . import progaudit

    baseline = progaudit.load_jaxpr_baseline(args.jaxpr_baseline)
    adjacency = None
    if args.adjacency:
        with open(args.adjacency, encoding="utf-8") as f:
            adjacency = json.load(f).get("adjacency") or None
    report = progaudit.fusion_report(baseline, adjacency)
    if args.format == "json":
        print(json.dumps(report, indent=2))
    else:
        chain = report["admission_chain"]
        print(
            "admission chain "
            + " -> ".join(chain["ops"])
            + f": ~{chain['predicted_saved_bytes']} B/round saved, "
            f"{chain['dispatches_collapsed']} dispatches collapsed"
        )
        for r in report["pairs"]:
            print(
                f"{r['producer']} -> {r['consumer']}  "
                f"[{r['source']}, x{r['count']}]  "
                f"~{r['saved_bytes_per_dispatch']} B/dispatch, "
                f"total ~{r['predicted_saved_bytes']} B"
            )
        if not report["pairs"]:
            print("no rankable pairs (is tool/jaxpr_baseline.json present?)")
    return 0 if report["pairs"] else 1


if __name__ == "__main__":
    sys.exit(main())

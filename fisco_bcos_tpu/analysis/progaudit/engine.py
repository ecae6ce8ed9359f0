"""The audit engine: inventory × PROGSPEC → traced, fingerprinted,
cost-modeled programs, plus the ``tool/jaxpr_baseline.json`` diff.

Every module that defines a jitted program also declares a ``PROGSPEC``
dict next to it (enforced by the `program-coherence` checker): traced-def
qualname → either an input-shape declaration or a skip reason::

    PROGSPEC = {
        "keccak256_blocks": {
            "bucket": 256,
            "inputs": lambda b: [((b, 1, 17, 2), "uint32"), ((b,), "int32")],
        },
        "_device_root_fn.run": {
            "bucket": 256,
            "call": lambda b: _device_root_fn(b, 16),
            "inputs": lambda b: [((b, 32), "uint8")],
        },
        "sharded_verify.local": {"skip": "needs a multi-device mesh"},
    }

``bucket`` is the canonical batch the program is audited at — an explicit
ladder rung, deliberately independent of ``FISCO_TEST_BUCKET`` so the
committed fingerprints do not depend on the environment. ``attr`` names
the module attribute to trace when it differs from the qualname; ``call``
builds the callable (program makers like ``merkle._device_root_fn``).
``slow: True`` marks programs whose *trace* alone is minutes-class (the
BLS pairing Miller loop unrolls ~100k limb eqns): they are fingerprinted
into the baseline by ``--update-jaxpr-baseline`` / ``--jaxpr-full`` and
skipped by default audits, which still verify their baseline PRESENCE
via the coverage check.

The audit never executes device code: ``jax.make_jaxpr`` over
``ShapeDtypeStruct`` inputs only. tests/test_progaudit.py pins the
compile ledger at zero entries during an audit.
"""

from __future__ import annotations

import importlib
import json
import os

from ..core import REPO_ROOT
from .fingerprint import explain_change, fingerprint

DEFAULT_JAXPR_BASELINE = os.path.join(REPO_ROOT, "tool", "jaxpr_baseline.json")


def _key(rec: dict) -> str:
    return f"{rec['file']}:{rec['qualname']}"


def inventory_keys(root: str | None = None) -> list[str]:
    """Sorted ``file:qualname`` keys of the FULL jitmap inventory — the
    universe the baseline must cover and may not exceed (stale guard).
    Pure AST: no imports of the subject modules, no jax."""
    from .. import jitmap

    return sorted(_key(rec) for rec in jitmap.inventory(root))


def _module_specs(relpath: str) -> tuple[dict, object]:
    mod_name = relpath[:-3].replace("/", ".")
    module = importlib.import_module(mod_name)
    return getattr(module, "PROGSPEC", {}) or {}, module


def _resolve_callable(module, qualname: str, spec: dict, bucket: int):
    if "call" in spec:
        return spec["call"](bucket)
    attr = spec.get("attr", qualname)
    fn = module
    for part in attr.split("."):
        fn = getattr(fn, part)
    return fn


def audit(
    root: str | None = None,
    programs: list[str] | None = None,
    include_slow: bool = False,
) -> dict:
    """Abstract-eval the inventory (or the ``programs`` subset, matched by
    ``file:qualname`` key or bare qualname) under each program's declared
    bucket. Returns::

        {"programs": {key: entry}, "failures": [{key, error}],
         "missing_spec": [key...], "inventory": [all keys],
         "not_traced": [keys skipped by slow/subset filtering]}

    A traced entry carries fingerprint + summary histograms + cost; a
    spec-skipped entry carries its reason. ``inventory`` always lists the
    FULL key set so the stale/coverage checks work on subset audits.
    """
    import jax

    from .. import jitmap
    from .costmodel import cost

    records = jitmap.inventory(root)
    all_keys = sorted(_key(r) for r in records)
    wanted = set(programs) if programs is not None else None

    out: dict = {
        "programs": {},
        "failures": [],
        "missing_spec": [],
        "inventory": all_keys,
        "not_traced": [],
    }
    spec_cache: dict[str, tuple[dict, object]] = {}
    for rec in sorted(records, key=_key):
        key = _key(rec)
        if wanted is not None and key not in wanted and rec[
            "qualname"
        ] not in wanted:
            out["not_traced"].append(key)
            continue
        relpath = rec["file"]
        if relpath not in spec_cache:
            try:
                spec_cache[relpath] = _module_specs(relpath)
            except Exception as e:
                out["failures"].append(
                    {"key": key, "error": f"import failed: {e}"}
                )
                continue
        specs, module = spec_cache[relpath]
        spec = specs.get(rec["qualname"])
        if spec is None:
            out["missing_spec"].append(key)
            continue
        if "skip" in spec:
            out["programs"][key] = {"skip": spec["skip"]}
            continue
        if spec.get("slow") and not include_slow and (
            wanted is None or key not in wanted
        ):
            out["not_traced"].append(key)
            continue
        bucket = int(spec["bucket"])
        try:
            fn = _resolve_callable(module, rec["qualname"], spec, bucket)
            avals = [
                jax.ShapeDtypeStruct(tuple(shape), dtype)
                for shape, dtype in spec["inputs"](bucket)
            ]
            closed = jax.make_jaxpr(fn)(*avals)
        except Exception as e:
            out["failures"].append(
                {
                    "key": key,
                    "error": f"abstract eval failed at bucket {bucket}: "
                    f"{type(e).__name__}: {e}",
                }
            )
            continue
        digest, summary = fingerprint(closed)
        entry = {"bucket": bucket, "fingerprint": digest}
        entry.update(summary)
        entry.update(cost(closed))
        if spec.get("slow"):
            entry["slow"] = True
        out["programs"][key] = entry
    return out


# -- baseline ---------------------------------------------------------------


def load_jaxpr_baseline(path: str | None = None) -> dict:
    path = path or DEFAULT_JAXPR_BASELINE
    if not os.path.exists(path):
        return {"programs": {}}
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def save_jaxpr_baseline(result: dict, path: str | None = None) -> None:
    path = path or DEFAULT_JAXPR_BASELINE
    data = {
        "_comment": "Canonical jaxpr fingerprints + static costs per "
        "inventoried device program (see docs/static_analysis.md). "
        "Regenerate with: python -m fisco_bcos_tpu.analysis "
        "--update-jaxpr-baseline (minutes-class: traces the BLS pairing "
        "programs). Review the diff — a changed fingerprint is a changed "
        "program.",
        "programs": {
            k: result["programs"][k] for k in sorted(result["programs"])
        },
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=False)
        f.write("\n")


def diff_audit(result: dict, baseline: dict) -> dict:
    """Audit result × baseline → the failure surface, all four ways:

    - ``new``: audited program with no baseline entry;
    - ``stale``: baseline entry whose program left the inventory (the
      stale-key guard — computed against the FULL inventory, so subset
      audits still catch deletions);
    - ``changed``: fingerprint / bucket / skip-status / dtype-histogram
      drift, each with a per-primitive explanation;
    - ``missing``: inventory program absent from the baseline (coverage
      gap — every program must be fingerprinted, slow ones included);

    plus the audit's own ``failures`` (abstract-eval errors) and
    ``missing_spec``. ``ok`` is True only when every list is empty.
    """
    base_progs = baseline.get("programs", {})
    inv = set(result["inventory"])
    audited = result["programs"]
    new = sorted(k for k in audited if k not in base_progs)
    stale = sorted(k for k in base_progs if k not in inv)
    missing = sorted(k for k in inv if k not in base_progs)
    changed: list[dict] = []
    for key in sorted(set(audited) & set(base_progs)):
        cur, old = audited[key], base_progs[key]
        if ("skip" in cur) != ("skip" in old):
            changed.append(
                {
                    "key": key,
                    "explanation": f"skip status changed: "
                    f"{old.get('skip')!r} -> {cur.get('skip')!r}",
                }
            )
            continue
        if "skip" in cur:
            continue
        if cur.get("bucket") != old.get("bucket"):
            changed.append(
                {
                    "key": key,
                    "explanation": f"audit bucket moved "
                    f"{old.get('bucket')} -> {cur.get('bucket')}",
                }
            )
        elif cur["fingerprint"] != old.get("fingerprint"):
            changed.append(
                {
                    "key": key,
                    "explanation": "fingerprint "
                    f"{old.get('fingerprint')} -> {cur['fingerprint']}: "
                    + explain_change(old, cur),
                }
            )
        elif cur.get("dtypes") != old.get("dtypes"):
            # unreachable when fingerprints match (dtypes hash in), but
            # the pin is explicit: histogram drift names itself
            changed.append(
                {
                    "key": key,
                    "explanation": "dtype histogram drift: "
                    + explain_change(old, cur),
                }
            )
    return {
        "ok": not (
            new or stale or missing or changed or result["failures"]
            or result["missing_spec"]
        ),
        "new": new,
        "stale": stale,
        "missing": missing,
        "changed": changed,
        "failures": result["failures"],
        "missing_spec": result["missing_spec"],
    }

"""Fusion-edge report: which adjacent programs are worth merging.

Joins two sources:

- **static** producer/consumer signatures from the jaxpr baseline (each
  program's boundary ``bytes_in``/``bytes_out`` — what a merge would stop
  round-tripping through the host), plus the ADMISSION_CHAIN catalog: the
  dispatch sequence the DevicePlane runs per admission batch today
  (keccak digests → secp256k1 recover → secp256k1 verify → host dedup
  key), which is exactly the ROADMAP's fused-admission work-list;
- **measured** dispatch adjacency from the device observatory's ledger
  (``CompileLedger.adjacency()``: back-to-back op pairs counted at
  ``device_span`` exit and DevicePlane dispatch), when a live ledger or a
  saved ``GET /device`` document provides one — measured counts
  weight the static edges by how often they actually ran in the flood.

Per pair, the predicted saving is ``min(producer bytes_out, consumer
bytes_in)`` — the largest boundary transfer a merge can possibly remove
(the true overlap needs argument-level matching; this upper bound ranks
pairs the same way) — times the dispatch count, plus one saved dispatch
per occurrence. Rows sort by predicted total saved bytes.
"""

from __future__ import annotations

_KECCAK = "fisco_bcos_tpu/ops/keccak.py:keccak256_blocks"
_RECOVER = "fisco_bcos_tpu/ops/secp256k1.py:_recover_xla"
_VERIFY = "fisco_bcos_tpu/ops/secp256k1.py:_verify_xla"
_ADMISSION = "fisco_bcos_tpu/crypto/admission.py:_admission_packed"

# device_span / DevicePlane op label -> baseline program key. Plane labels
# ("hash.<Hasher>", "verify.<scheme>") and wrapper labels ("keccak256",
# "secp256k1_verify") both appear in adjacency streams.
OP_PROGRAMS = {
    "keccak256": _KECCAK,
    "hash.keccak256": _KECCAK,
    "sha256": "fisco_bcos_tpu/ops/sha256.py:sha256_blocks",
    "hash.sha256": "fisco_bcos_tpu/ops/sha256.py:sha256_blocks",
    "sm3": "fisco_bcos_tpu/ops/sm3.py:sm3_blocks",
    "hash.sm3": "fisco_bcos_tpu/ops/sm3.py:sm3_blocks",
    "poseidon": "fisco_bcos_tpu/ops/poseidon.py:poseidon_blocks",
    "hash.poseidon": "fisco_bcos_tpu/ops/poseidon.py:poseidon_blocks",
    "secp256k1_verify": _VERIFY,
    "verify.secp256k1": _VERIFY,
    "secp256k1_recover": _RECOVER,
    "recover.secp256k1": _RECOVER,
    "sm2_verify": "fisco_bcos_tpu/ops/sm2.py:_verify_xla",
    "verify.sm2": "fisco_bcos_tpu/ops/sm2.py:_verify_xla",
    "ed25519_verify": "fisco_bcos_tpu/ops/ed25519.py:_verify_xla",
    "verify.ed25519": "fisco_bcos_tpu/ops/ed25519.py:_verify_xla",
    "sender_address": "fisco_bcos_tpu/ops/address.py:sender_address_device",
    "merkle_root": "fisco_bcos_tpu/ops/merkle.py:_device_root_fn.run",
    "merkle_tree": "fisco_bcos_tpu/ops/merkle.py:_device_root_fn.run",
    "merkle_tree.keccak256": "fisco_bcos_tpu/ops/merkle.py:_device_root_fn.run",
    "merkle_tree.sm3": "fisco_bcos_tpu/ops/merkle.py:_device_tree_fn.tree",
    "admission": _ADMISSION,
    "admission_native": _ADMISSION,
    "admission_sharded": _ADMISSION,
}

# dedup is host code (txpool seen-set over the digest), not a program:
# model it as a pseudo-consumer whose bytes_in is the digest column it
# pulls off the device per lane.
_DEDUP = "host:dedup_key"
_DEDUP_BYTES_PER_LANE = 32  # keccak256 digest

# the per-batch dispatch sequence of today's UNFUSED admission path — the
# chain the ROADMAP's fused admission program collapses into one dispatch
ADMISSION_CHAIN = ("keccak256", "secp256k1_recover", "secp256k1_verify",
                   "dedup_key")


def _program_for(op: str):
    if op == "dedup_key":
        return _DEDUP
    return OP_PROGRAMS.get(op)


def _bytes(baseline_progs: dict, prog_key: str, field: str, bucket_hint: int):
    if prog_key == _DEDUP:
        return _DEDUP_BYTES_PER_LANE * bucket_hint if field == "bytes_in" else 0
    entry = baseline_progs.get(prog_key)
    if not entry or "skip" in entry:
        return None
    return entry.get(field)


def fusion_report(
    baseline: dict,
    adjacency: dict | None = None,
    top: int | None = None,
) -> dict:
    """Ranked mergeable program pairs (see module doc).

    ``baseline`` is the loaded ``tool/jaxpr_baseline.json``; ``adjacency``
    maps ``"op_a->op_b"`` to a measured dispatch count (from
    ``CompileLedger.adjacency()`` or a saved device artifact). The static
    ADMISSION_CHAIN edges are always present — with count 1 when the
    flood has not been measured — so the report is actionable from the
    committed baseline alone.
    """
    progs = baseline.get("programs", {})
    # op-pair -> measured count; seed the static chain at count>=1
    pairs: dict[tuple[str, str], dict] = {}
    for a, b in zip(ADMISSION_CHAIN, ADMISSION_CHAIN[1:]):
        pairs[(a, b)] = {"count": 1, "source": "static-chain"}
    for edge, count in (adjacency or {}).items():
        if "->" not in edge:
            continue
        a, b = edge.split("->", 1)
        if _program_for(a) is None or _program_for(b) is None:
            continue
        rec = pairs.get((a, b))
        if rec is None:
            pairs[(a, b)] = {"count": int(count), "source": "measured"}
        else:
            rec["count"] = max(int(count), rec["count"])
            rec["source"] = "static-chain+measured"

    rows: list[dict] = []
    for (a, b), rec in pairs.items():
        pa, pb = _program_for(a), _program_for(b)
        entry_a = progs.get(pa, {}) if pa != _DEDUP else {}
        bucket = entry_a.get("bucket", 256)
        out_a = _bytes(progs, pa, "bytes_out", bucket)
        in_b = _bytes(progs, pb, "bytes_in", bucket)
        if out_a is None or in_b is None:
            continue  # program not in the baseline (yet) — nothing to rank
        saved = min(out_a, in_b)
        rows.append(
            {
                "producer": a,
                "consumer": b,
                "producer_program": pa,
                "consumer_program": pb,
                "count": rec["count"],
                "source": rec["source"],
                "saved_bytes_per_dispatch": saved,
                "predicted_saved_bytes": saved * rec["count"],
                "dispatches_saved": rec["count"],
            }
        )
    rows.sort(
        key=lambda r: (-r["predicted_saved_bytes"], r["producer"],
                       r["consumer"])
    )
    if top is not None:
        rows = rows[:top]
    chain_rows = [
        r for r in rows
        if (r["producer"], r["consumer"])
        in set(zip(ADMISSION_CHAIN, ADMISSION_CHAIN[1:]))
    ]
    return {
        "pairs": rows,
        "admission_chain": {
            "ops": list(ADMISSION_CHAIN),
            "edges": chain_rows,
            "predicted_saved_bytes": sum(
                r["predicted_saved_bytes"] for r in chain_rows
            ),
            "dispatches_collapsed": len(ADMISSION_CHAIN) - 1,
        },
    }

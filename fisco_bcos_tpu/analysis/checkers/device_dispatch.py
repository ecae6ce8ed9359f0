"""Device-dispatch discipline: device crypto/hash entry points may only be
called from the DevicePlane seams.

PR 3 centralized ALL device crypto dispatch behind the plane's seams —
``crypto/suite.py`` batch methods, ``crypto/admission.admit_batch``, the
``ops/`` host wrappers themselves, ``device/plane.py`` and the sharded
wrappers in ``parallel/sharding.py``. A module elsewhere importing an ops
kernel and dispatching its own batch silently forks the dispatch
discipline: no coalescing, no priority lane, no breaker fallback, and its
ad-hoc batch shapes re-open the recompile churn the bucket ladder closed.

Rule: importing a device-kernel module (``ops.keccak``, ``ops.secp256k1``,
``ops.sm2``, ``ops.sm3``, ``ops.sha256``, ``ops.ed25519``, ``ops.merkle``,
``ops.address``) — or any *device entry* name from one —
outside the seam allowlist is a finding. Host-side helpers are exempt:
``ops.hash_common``/``ops.bigint``/``ops.limb`` everywhere, and the named
host-tree classes from ``ops.merkle`` (``MerkleTree``/``MerkleProofItem``,
which ledger/lightnode legitimately use for proofs).

Second rule, the layering under the seams: ``crypto/`` imports ``device/``,
``ops/`` and ``parallel/``, never the reverse. A module in one of those
three directories that imports ``fisco_bcos_tpu.crypto`` points an arrow
up (it is how the dispatch policy came to be known by five modules).
``crypto.ref`` is exempt: the plain reference is a leaf of pure Python that
imports nothing of the package, and ``ops/`` takes its curve constants and
host hashes from it.
"""

from __future__ import annotations

import ast

from ..core import Checker, Finding, Source, qualnames

# device-kernel modules: importing these implies device dispatch
DEVICE_MODULES = {
    "keccak", "sha256", "sm3", "sm2", "secp256k1", "ed25519",
    "merkle", "address", "bls12_381", "poseidon",
}
# names importable from device modules that are host-side only
HOST_SAFE_NAMES = {
    "MerkleTree", "MerkleProofItem", "bucket_leaves", "bind_root",
}
# modules allowed to dispatch device programs (the seams)
SEAM_PREFIXES = (
    "fisco_bcos_tpu/ops/",
    "fisco_bcos_tpu/crypto/",
    "fisco_bcos_tpu/device/",
    "fisco_bcos_tpu/parallel/",
    "fisco_bcos_tpu/analysis/",  # the checkers read, never dispatch
)


# directories below the crypto seams: nothing in them imports crypto/
LOWER_LAYERS = {"device", "ops", "parallel"}


def _imported_crypto(node: ast.AST) -> str | None:
    """The ``crypto`` module an import names (``crypto.suite``, ``crypto``),
    None for anything else and for the exempt ``crypto.ref``."""
    names: list[str] = []
    if isinstance(node, ast.ImportFrom):
        mod = node.module or ""
        if node.level == 0 and not mod.startswith("fisco_bcos_tpu"):
            return None
        parts = [p for p in mod.split(".") if p != "fisco_bcos_tpu" and p]
        # from .. import crypto / from ..crypto import suite: the names count
        names = [".".join(parts + [a.name]) for a in node.names]
    elif isinstance(node, ast.Import):
        names = [
            a.name.removeprefix("fisco_bcos_tpu.")
            for a in node.names
            if a.name.startswith("fisco_bcos_tpu.")
        ]
    for name in names:
        parts = name.split(".")
        if parts[0] == "crypto" and parts[1:2] != ["ref"]:
            return ".".join(parts[:2])
    return None


def _imported_device_module(node: ast.AST) -> tuple[str, list[str]] | None:
    """(device module name, imported names ('' = whole module)) or None."""
    if isinstance(node, ast.ImportFrom) and node.module:
        parts = node.module.split(".")
        # from ..ops import keccak / from ..ops.merkle import merkle_root
        if parts[-1] in DEVICE_MODULES and (len(parts) == 1 or "ops" in parts):
            return parts[-1], [a.name for a in node.names]
        if parts[-1] == "ops" or parts[-1:] == ["ops"]:
            mods = [a.name for a in node.names if a.name in DEVICE_MODULES]
            if mods:
                return mods[0] if len(mods) == 1 else ",".join(mods), [""]
    elif isinstance(node, ast.Import):
        for a in node.names:
            parts = a.name.split(".")
            if parts[-1] in DEVICE_MODULES and "ops" in parts:
                return parts[-1], [""]
    return None


class DeviceDispatchChecker(Checker):
    name = "device-dispatch"
    description = (
        "device crypto/hash kernels import only inside the DevicePlane "
        "seams (ops/crypto/device/parallel) — everyone else uses the suite"
    )

    def run(self, sources: list[Source]) -> list[Finding]:
        out: list[Finding] = []
        for src in sources:
            if LOWER_LAYERS.intersection(src.relpath.split("/")[:-1]):
                out.extend(self._upward_imports(src))
            if src.relpath.startswith(SEAM_PREFIXES):
                continue
            qn = qualnames(src.tree)
            for node in ast.walk(src.tree):
                hit = _imported_device_module(node)
                if hit is None:
                    continue
                mod, names = hit
                offenders = [
                    n for n in names if n == "" or n not in HOST_SAFE_NAMES
                ]
                if not offenders:
                    continue
                if src.waived(node.lineno, self.name):
                    continue
                what = ", ".join(n or f"module {mod}" for n in offenders)
                out.append(
                    self.finding(
                        src,
                        node,
                        qn.get(node, ""),
                        f"import-{mod}",
                        f"device kernel `{what}` (ops.{mod}) imported outside "
                        "the DevicePlane seams (crypto/suite, crypto/admission, "
                        "ops/, device/, parallel/) — dispatch must route "
                        "through the plane",
                    )
                )
        return out

    def _upward_imports(self, src: Source) -> list[Finding]:
        out: list[Finding] = []
        qn = qualnames(src.tree)
        for node in ast.walk(src.tree):
            mod = _imported_crypto(node)
            if mod is None or src.waived(node.lineno, self.name):
                continue
            out.append(
                self.finding(
                    src,
                    node,
                    qn.get(node, ""),
                    f"imports-up-{mod}",
                    f"`{mod}` imported from below the crypto seams: device/, "
                    "ops/ and parallel/ import nothing of crypto/ but the "
                    "plain reference (crypto.ref) — hand the value down as "
                    "an argument, or move it to where both can reach it",
                )
            )
        return out

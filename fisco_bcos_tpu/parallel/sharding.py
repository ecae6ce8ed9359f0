"""Device-mesh sharding for the batch crypto plane.

The reference scales its hot verify loops with ``tbb::parallel_for`` over CPU
threads (bcos-txpool/sync/TransactionSync.cpp:521-553) and its state hash the
same way (bcos-table/src/StateStorage.h:457-486); multi-machine scale comes
from Tars RPC process sharding. The TPU-native equivalent is a
``jax.sharding.Mesh``: signature/hash batches are sharded over the ``data``
axis (lanes ride ICI, not DCN), per-shard results are combined with XLA
collectives (``psum`` for validity counts and the XOR state root), and the
validity bitmap is returned fully replicated — the moral equivalent of the
all-gather of admission results every consensus participant needs.

No NCCL/MPI exists here by design: collectives are emitted by XLA from the
sharding annotations (see SURVEY.md §2.8 "Distributed communication backend").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import secp256k1

DATA_AXIS = "data"


def make_mesh(n_devices: int | None = None, axis_name: str = DATA_AXIS) -> Mesh:
    """1-D device mesh over the first `n_devices` local devices."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if n > len(devs):
        raise ValueError(
            f"make_mesh: {n} devices requested, only {len(devs)} available"
        )
    return Mesh(np.asarray(devs[:n]), (axis_name,))


def sharded_verify(mesh: Mesh, axis_name: str = DATA_AXIS):
    """Batch-sharded secp256k1 verify.

    Returns a jitted fn (z, r, s, qx, qy) -> (ok bool[B], n_valid int32[]);
    inputs [B, 16] limb tensors with B divisible by the mesh size. `ok` comes
    back replicated (all-gather), `n_valid` via psum.
    """

    def local(z, r, s, qx, qy):
        ok = secp256k1.verify_device(z, r, s, qx, qy)
        n_valid = jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), axis_name)
        return jax.lax.all_gather(ok, axis_name, tiled=True), n_valid

    spec = P(axis_name)
    f = jax.shard_map(
        local,
        mesh=mesh,
        check_vma=False,
        in_specs=(spec, spec, spec, spec, spec),
        out_specs=(P(), P()),
    )
    return jax.jit(f)


def sharded_admission(mesh: Mesh, core, axis_name: str = DATA_AXIS):
    """Batch-sharded fused admission (hash → recover → address), the sharded
    form of crypto.admission.admission_step; `core` is that module's unjitted
    ``admission_core``, handed down by the caller (this layer sits under
    crypto/ and imports nothing of it).

    Returns a jitted fn (blocks, nblocks, r, s, v) ->
    (addr [B, 20] replicated, ok bool[B] replicated, n_valid int32[]).
    """

    def local(blocks, nblocks, r, s, v):
        addr, ok, _qx, _qy, _z = core(blocks, nblocks, r, s, v)
        n_valid = jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), axis_name)
        return (
            jax.lax.all_gather(addr, axis_name, tiled=True),
            jax.lax.all_gather(ok, axis_name, tiled=True),
            n_valid,
        )

    spec = P(axis_name)
    f = jax.shard_map(
        local,
        mesh=mesh,
        check_vma=False,
        in_specs=(spec, spec, spec, spec, spec),
        out_specs=(P(), P(), P()),
    )
    return jax.jit(f)


def sharded_admission_packed(mesh: Mesh, body, axis_name: str = DATA_AXIS):
    """Fan-out form of a packed one-transfer admission program — the
    DevicePlane's multi-device leg for merged batches above its per-device
    threshold. `body` is the unjitted packed program of one suite
    (crypto.admission: ``_admission_packed`` or ``_sm_admission_packed``);
    every operand is batch-leading.

    Each device runs the fused admission body over its batch shard and
    packs locally; the [B, 117] uint8 result (addr ‖ ok ‖ pubkey ‖ tx_hash)
    rides ONE all_gather, so the host still pays a single transfer.
    Bit-identical to the single-chip program lane-for-lane (the body is the
    single-chip one verbatim; only the batch partitioning differs).

    Returns a jitted fn (the body's operands) -> [B, 117] uint8 replicated;
    B divisible by the mesh size (the bucket ladder guarantees it for
    power-of-two meshes)."""

    def admission_shard(*operands):
        return jax.lax.all_gather(body(*operands), axis_name, tiled=True)

    f = jax.shard_map(
        admission_shard,
        mesh=mesh,
        check_vma=False,
        in_specs=P(axis_name),
        out_specs=P(),
    )
    return jax.jit(f)


def sharded_sm2_verify(mesh: Mesh, axis_name: str = DATA_AXIS):
    """Batch-sharded SM2 verify (the national-crypto lane of the
    verification plane).

    Returns a jitted fn (e, r, s, qx, qy) -> (ok bool[B] replicated,
    n_valid int32[]); inputs [B, 16] plain limb tensors, e = SM3(ZA ‖ M)
    computed host-side. B divisible by the mesh size."""
    from ..ops import sm2

    def local(e, r, s, qx, qy):
        ok = sm2.verify_device(e, r, s, qx, qy)
        n_valid = jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), axis_name)
        return jax.lax.all_gather(ok, axis_name, tiled=True), n_valid

    spec = P(axis_name)
    f = jax.shard_map(
        local,
        mesh=mesh,
        check_vma=False,
        in_specs=(spec,) * 5,
        out_specs=(P(), P()),
    )
    return jax.jit(f)


def sharded_ed25519_verify(mesh: Mesh, axis_name: str = DATA_AXIS):
    """Batch-sharded Ed25519 verify.

    Returns a jitted fn (s, k_neg, a_y, a_sign, r_y, r_sign) ->
    (ok bool[B] replicated, n_valid int32[]): [B, 16] limb tensors for
    s/k_neg/a_y/r_y, [B] int32 signs — the same shapes
    ops.ed25519._verify_xla takes (host computes the SHA-512 challenges)."""
    from ..ops import ed25519 as ed

    b_table = jnp.asarray(ed.b_comb_table())

    def local(s, k_neg, a_y, a_sign, r_y, r_sign):
        ok = ed.verify_core(s.T, k_neg.T, a_y.T, a_sign, r_y.T, r_sign, b_table)
        n_valid = jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), axis_name)
        return jax.lax.all_gather(ok, axis_name, tiled=True), n_valid

    spec = P(axis_name)
    f = jax.shard_map(
        local,
        mesh=mesh,
        check_vma=False,
        in_specs=(spec,) * 6,
        out_specs=(P(), P()),
    )
    return jax.jit(f)


def sharded_merkle_root(mesh: Mesh, width: int = 16, axis_name: str = DATA_AXIS):
    """Batch-sharded wide-merkle keccak root.

    Each shard folds its leaf slice down to ONE subtree node locally (the
    bulk of the hashing — level 0 dominates), the per-shard nodes ride one
    all_gather, and the small top of the tree is folded replicated.
    Bit-identical to the single-device tree when the per-shard leaf count
    is a power of `width` (then each shard's fold IS the corresponding
    tree node) — the caller picks N = D·width^k; other shapes belong on
    the unsharded path.

    Emits the bucket-PADDED tree root (callers pad N to
    ops.merkle.bucket_leaves and finish with ops.merkle.bind_root — the
    count binding is one host hash, not worth a collective).

    Returns a jitted fn (leaves [N, 32] uint8) -> [32] uint8."""
    from ..ops.merkle import _device_level

    def local(leaves):
        cur = leaves
        while cur.shape[0] > 1:
            cur = _device_level(cur, width)
        nodes = jax.lax.all_gather(cur, axis_name, tiled=True)  # [D, 32]
        while nodes.shape[0] > 1:
            nodes = _device_level(nodes, width)
        return nodes[0]

    f = jax.shard_map(
        local, mesh=mesh, check_vma=False,
        in_specs=(P(axis_name),), out_specs=P(),
    )
    return jax.jit(f)


def sharded_qc_check(mesh: Mesh, axis_name: str = DATA_AXIS):
    """Batch-sharded block-QC signature-list check — the reference's #2
    hot loop (bcos-pbft BlockValidator.cpp:141-177: verify every committee
    signature on the header hash, sum the signers' weights).

    Returns a jitted fn (z, r, s, qx, qy [B, 16] limbs, weights [B] int32)
    -> (ok bool[B] replicated, weight int32[] — psum of VALID signers'
    weights, compared against the quorum by the caller)."""

    def local(z, r, s, qx, qy, weights):
        ok = secp256k1.verify_device(z, r, s, qx, qy)
        weight = jax.lax.psum(
            jnp.sum(jnp.where(ok, weights, 0).astype(jnp.int32)), axis_name
        )
        return jax.lax.all_gather(ok, axis_name, tiled=True), weight

    spec = P(axis_name)
    f = jax.shard_map(
        local,
        mesh=mesh,
        check_vma=False,
        in_specs=(spec,) * 6,
        out_specs=(P(), P()),
    )
    return jax.jit(f)


def sharded_state_root(mesh: Mesh, axis_name: str = DATA_AXIS):
    """Order-independent XOR state root over sharded entry digests.

    The reference folds dirty-entry hashes with XOR under tbb
    (StateStorage.h:457-486 — XOR makes the root order-independent, which is
    exactly what makes it shardable). fn: digests [B, 8] uint32 -> [8] uint32.
    """

    def local(digests):
        partial = jnp.bitwise_xor.reduce(digests, axis=0)
        # XOR-reduce across shards: psum has no xor variant, so gather + fold.
        allp = jax.lax.all_gather(partial, axis_name)
        return jnp.bitwise_xor.reduce(allp, axis=0)

    f = jax.shard_map(local, mesh=mesh, in_specs=(P(axis_name),), out_specs=P(), check_vma=False)
    return jax.jit(f)


# -- progaudit shape spec: sharded variants trace against the deployment's
# mesh (device count + fan-out threshold) — no canonical single-host shape.
_SHARDED_SKIP = "needs a multi-device mesh (shapes depend on deployment fan-out)"
PROGSPEC = {
    "sharded_verify.local": {"skip": _SHARDED_SKIP},
    "sharded_admission.local": {"skip": _SHARDED_SKIP},
    "sharded_admission_packed.admission_shard": {"skip": _SHARDED_SKIP},
    "sharded_sm2_verify.local": {"skip": _SHARDED_SKIP},
    "sharded_ed25519_verify.local": {"skip": _SHARDED_SKIP},
    "sharded_merkle_root.local": {"skip": _SHARDED_SKIP},
    "sharded_qc_check.local": {"skip": _SHARDED_SKIP},
    "sharded_state_root.local": {"skip": _SHARDED_SKIP},
}

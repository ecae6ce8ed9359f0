"""Fused tx-admission crypto step — the flagship device program.

One device program performs, for a whole block of transactions, what the
reference does one tx at a time on RPC/txpool threads
(``TxValidator::verify`` bcos-txpool/txpool/validator/TxValidator.cpp:27-69 →
``Transaction::verify()`` bcos-framework/protocol/Transaction.h:64-84):

    tx hash (keccak256)  →  ECDSA recover  →  sender = right160(keccak(pub))

The batch enters as pre-padded keccak block tensors plus signature limb
tensors, and leaves as (sender addresses, validity bitmap, recovered pubkeys).
Invalid lanes never raise — they lower a validity bit (consensus code must be
total). See also the #1 batch-verify hot loop in the reference,
bcos-txpool/sync/TransactionSync.cpp:521-553 (tbb::parallel_for over verify).

A national-crypto chain (``sm_crypto=true``) runs the same step as

    tx hash (SM3)  →  e = SM3(ZA ‖ hash)  →  SM2 verify of the carried key
    →  sender = right160(SM3(pub))

(:func:`sm_admission_core`; the 128-byte signature r ‖ s ‖ pub carries the
key, bcos-crypto signature/sm2/SM2Crypto.cpp:29-91). What differs between the
two suites is one :class:`_Body`; the device leg below (marshal, mesh fan-out,
packed result) is written once against it, and where a batch runs — plane,
policy, breaker — is device/dispatch.py's.
"""

from __future__ import annotations

import functools
import os
from collections.abc import Callable
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..device.dispatch import BatchOp, dispatch
from ..ops import keccak, limb, secp256k1, sm2, sm3
from ..ops.address import sender_address_device, sm_sender_address_device
from ..ops.bigint import digest_words_be_to_limbs, digest_words_le_to_limbs
from ..ops.hash_common import pad_keccak, pad_md64


def admission_core(blocks, nblocks, r, s, v):
    """The fused admission body, unjitted — shared verbatim by the single-chip
    jit (``admission_step``) and the sharded wrapper
    (parallel.sharding.sharded_admission), so the two paths cannot drift.

    blocks [B, M, 17, 2] + nblocks [B] are the pre-padded keccak form of each
    tx's signed payload; (r, s) [B, 16] limbs and v [B] int32 are the 65-byte
    signature split.

    Returns (addr [B, 20] uint32 bytes, ok bool[B], qx, qy, z [B, 16] limbs) —
    z is the tx hash as limbs, returned so callers reuse the digests instead
    of re-hashing the payloads in a second device pass.
    """
    words = keccak.keccak256_blocks(blocks, nblocks)
    z = digest_words_le_to_limbs(words)
    qx, qy, ok = secp256k1.recover_device(z, r, s, v)
    addr = sender_address_device(qx, qy)
    return addr, ok, qx, qy, z


admission_step = jax.jit(admission_core)


def pack_admission_device(addr, ok, qx, qy, z):
    """Pack the admission outputs into one uint8 tensor
    [B, 117] = addr(20) ‖ ok(1) ‖ pubkey(64) ‖ tx_hash(32): each host fetch
    is a device sync plus a transfer, so the whole admission result crosses
    once instead of five times. Shared by the single-chip jit and
    the sharded wrapper (parallel.sharding.sharded_admission_packed)."""
    from ..ops.bigint import limbs_to_bytes_device

    return jnp.concatenate(
        [
            addr.astype(jnp.uint8),
            ok.astype(jnp.uint8)[:, None],
            limbs_to_bytes_device(qx).astype(jnp.uint8),
            limbs_to_bytes_device(qy).astype(jnp.uint8),
            limbs_to_bytes_device(z).astype(jnp.uint8),
        ],
        axis=1,
    )


def _in_tiles(whole, tile: int, *operands):
    """`whole` (a packed admission program: batch-leading operands ->
    [B, 117]) over `operands`, `tile` lanes at a time inside ONE program: the
    operands are padded to a whole number of tiles, `whole` runs over the
    tiles under ``lax.map`` (one body in the program and a loop around it;
    nothing is unrolled and nothing is dispatched twice), and the pad lanes
    are dropped before the result leaves. A pad lane repeats the batch's last
    row: every operand is batch-leading, so it is a lane of a kind the batch
    already holds (a signed payload, or one of the marshaller's own empty pad
    rows), it never raises, and no lane's answer depends on another's. One
    tile that holds the whole batch is `whole` itself, with no loop around
    it. The tile is the plan's (``_Body.plan``); only a test or the op
    profile hands in another."""
    lanes = operands[0].shape[0]
    tiles = -(-lanes // tile)
    if tiles == 1:
        return whole(*operands)
    pad = tiles * tile - lanes

    def stacked(o):  # [lanes, ...] -> [tiles, tile, ...]
        if pad:
            o = jnp.concatenate([o, jnp.broadcast_to(o[-1:], (pad,) + o.shape[1:])])
        return o.reshape((tiles, tile) + o.shape[1:])

    out = jax.lax.map(lambda tile_operands: whole(*tile_operands),
                      tuple(stacked(o) for o in operands))
    return out.reshape((tiles * tile,) + out.shape[2:])[:lanes]


def _admission_whole(blocks, nblocks, r, s, v):
    return pack_admission_device(*admission_core(blocks, nblocks, r, s, v))


def _admission_packed(blocks, nblocks, r, s, v):
    """The packed program as the plan of its lane count runs it
    (``limb.lane_plan``: a pure function of ``blocks.shape[0]``, known when
    the program is traced; the form of a tile is ``limb.lane_dense``'s, from
    the same table)."""
    tile = limb.lane_plan(blocks.shape[0]).tile
    return _in_tiles(_admission_whole, tile, blocks, nblocks, r, s, v)


admission_step_packed = jax.jit(_admission_packed)


def sm_admission_core(blocks, nblocks, r, s, qx, qy):
    """The fused SM2/SM3 admission body, unjitted like :func:`admission_core`
    and shared the same way by the single-chip jit and the sharded wrapper.

    blocks [B, M, 16] + nblocks [B] are the pre-padded SM3 form of each tx's
    signed payload (``hash_common.pad_md64``); r, s and the carried public key
    (qx, qy) are [B, 16] limbs split from the 128-byte signature. ZA, e and
    the address input are assembled from the limbs here, on the device.

    Returns the tuple :func:`admission_core` returns. A rejected lane (range
    check, carried key not a point of the curve, or not the signer's) comes
    back with address and key zeroed; z is always the payload's digest."""
    h = sm3.sm3_blocks(blocks, nblocks)
    e = digest_words_be_to_limbs(sm2.e_device(h, qx, qy))
    ok = sm2.verify_rows(e, r, s, qx, qy)
    keep = ok[:, None]
    addr = jnp.where(keep, sm_sender_address_device(qx, qy), 0)
    qx, qy = (jnp.where(keep, q, 0) for q in (qx, qy))
    return addr, ok, qx, qy, digest_words_be_to_limbs(h)


def _sm_admission_packed(blocks, nblocks, r, s, qx, qy):
    return pack_admission_device(
        *sm_admission_core(blocks, nblocks, r, s, qx, qy)
    )


sm_admission_step_packed = jax.jit(_sm_admission_packed)


def _admit_batch_native(payloads, sigs65):
    """Host-loop admission through the native C core (keccak → recover →
    address), bit-identical to the device program on valid lanes
    (tests/test_admission.py pins it). None when the native library is
    unavailable. ~0.3ms/sig — beats the DEVICE path outright when the jax
    backend is CPU XLA, and a device dispatch's fixed cost for small
    batches (device/dispatch.py holds the cutover)."""
    from .. import native_bind

    if native_bind.load() is None:
        return None
    n = len(payloads)
    hashes = [native_bind.keccak256(p) for p in payloads]
    pubs_raw, oks = native_bind.secp256k1_recover_batch(
        b"".join(hashes),
        np.ascontiguousarray(sigs65[:, :32]).tobytes(),
        np.ascontiguousarray(sigs65[:, 32:64]).tobytes(),
        np.ascontiguousarray(sigs65[:, 64]).tobytes(),
        n,
    )
    pubs = np.frombuffer(pubs_raw, dtype=np.uint8).reshape(n, 64).copy()
    ok = np.asarray(oks, dtype=bool)
    pubs[~ok] = 0
    senders = np.zeros((n, 20), dtype=np.uint8)
    for i in range(n):
        if ok[i]:
            senders[i] = np.frombuffer(
                native_bind.keccak256(pubs[i].tobytes())[-20:], dtype=np.uint8
            )
    digests = np.frombuffer(b"".join(hashes), dtype=np.uint8).reshape(n, 32)
    return senders, ok, pubs, digests


def _admit_batch_host_sm(payloads, sigs128, native: bool):
    """SM admission on the host (SM3 → SM2 verify of the carried key →
    address), lane for lane what the device program answers. ``native``
    verifies through the C core's batch loop and gives None where the library
    is missing; otherwise the per-item loop, which always answers (the
    breaker's fallback)."""
    from .suite import SM3, SM2Crypto

    sm3_hash, impl = SM3().hash, SM2Crypto()
    n = len(payloads)
    digests = np.frombuffer(
        b"".join(sm3_hash(p) for p in payloads), dtype=np.uint8
    ).reshape(n, 32)
    pubs = sigs128[:, 64:128]
    if native:
        ok = impl._native_verify(digests, pubs, sigs128)
        if ok is None:
            return None
    else:
        ok = impl._host_verify_loop(digests, pubs, sigs128)
    pubs = np.where(ok[:, None], pubs, 0).astype(np.uint8)
    senders = np.zeros((n, 20), dtype=np.uint8)
    for i in np.flatnonzero(ok):
        senders[i] = np.frombuffer(sm3_hash(pubs[i].tobytes())[12:], dtype=np.uint8)
    return senders, ok, pubs, digests


# -- the two suites' bodies ----------------------------------------------------


def _limb_operands(be: np.ndarray, bb: int) -> tuple[np.ndarray, ...]:
    """[B, 32·k] uint8 rows of k big-endian 256-bit values -> k operands
    [bb, 16] uint32 (16-bit limbs, least significant first, what
    ``bytes_be_to_limbs`` gives each value), zero rows behind the batch. One
    big-endian 16-bit view of the rows, written limb-reversed straight into
    the zeroed bucket-sized block the k operands are slices of."""
    n, k = be.shape[0], be.shape[1] // 32
    out = np.zeros((k, bb, 16), dtype=np.uint32)
    be16 = np.ascontiguousarray(be).view(">u2").reshape(n, k, 16)
    out[:, :n] = be16[:, :, ::-1].transpose(1, 0, 2)
    return tuple(out)


def _marshal_secp(payloads, sigs65, bb):
    v = np.zeros(bb, dtype=np.int32)
    v[: len(sigs65)] = sigs65[:, 64]
    return pad_keccak(payloads) + _limb_operands(sigs65[:, :64], bb) + (v,)


def _marshal_sm(payloads, sigs128, bb):
    return pad_md64(payloads) + _limb_operands(sigs128, bb)  # r, s, Px, Py


def _host_native_or_raise(payloads, sigs65):
    out = _admit_batch_native(payloads, sigs65)
    if out is None:
        raise RuntimeError("native admission unavailable for host fallback")
    return out


def _native_leg(native):
    """A body's native host loop as a leg: under the ``admission_native``
    span, shape_key pinned so it never reads as a compile; the op label keeps
    the dispatch split visible."""

    def run(payloads, sigs):
        from ..observability.device import device_span

        with device_span("admission_native", len(payloads), shape_key="native"):
            return native(payloads, sigs)

    return run


@dataclass(frozen=True, kw_only=True)
class _Body(BatchOp):
    """What one suite's fused admission is made of: the seam's description
    (label ``admission`` for either suite, the plane op, the three legs) and
    what the device leg, written once below the table, is built from."""

    op: str  # device_span op: spans, phases, items, compile-ledger episodes
    sig_len: int
    packed: Callable  # the unjitted program -> [B, 117]; the sharded wrapper's body
    step: Callable  # jax.jit(packed), through the module's name for it
    mblocks: Callable[[int], int]  # longest payload's bytes -> message blocks
    plan: Callable[[int], limb.LanePlan]  # a device's lanes -> how `packed` runs them
    marshal: Callable  # (payloads, sigs, bucket) -> the program's operands


# the device leg may fan out over the mesh; it names its body through the
# module, as `step` names its jit
_BODIES = {
    ("secp256k1", "keccak256"): _Body(
        "admission", "admission",
        lambda p, s: _admit_batch_device(p, s, allow_shard=True, body=_SECP),
        _native_leg(_admit_batch_native), _host_native_or_raise,
        op="admission", sig_len=65, packed=_admission_packed,
        step=lambda *operands: admission_step_packed(*operands),
        mblocks=lambda n: n // 136 + 1, marshal=_marshal_secp,
        plan=lambda lanes: limb.lane_plan(lanes),
    ),
    ("sm2", "sm3"): _Body(
        "admission", "admission.sm",
        lambda p, s: _admit_batch_device(p, s, allow_shard=True, body=_SM),
        _native_leg(functools.partial(_admit_batch_host_sm, native=True)),
        functools.partial(_admit_batch_host_sm, native=False),
        op="admission_sm", sig_len=128, packed=_sm_admission_packed,
        step=lambda *operands: sm_admission_step_packed(*operands),
        mblocks=lambda n: (n + 8) // 64 + 1, marshal=_marshal_sm,
        # no size is cheap enough to tile to (ops/limb.lane_plan's table)
        plan=limb.whole_plan,
    ),
}
_SECP = _BODIES["secp256k1", "keccak256"]
_SM = _BODIES["sm2", "sm3"]


def _body_of(suite) -> _Body | None:
    """The fused body of a suite (crypto.suite.CryptoSuite), None where it
    has none (ed25519); no suite means the default one."""
    if suite is None:
        return _SECP
    return _BODIES.get((suite.signature_impl.name, suite.hash_impl.name))


# -- multi-device fan-out -----------------------------------------------------


@dataclass(frozen=True)
class _Fanout:
    """A body's program over the local mesh, and where its operands go."""

    step: Callable  # jit(shard_map(body.packed)): operands -> [B, 117] on every device
    sharding: object  # NamedSharding(mesh, P(DATA_AXIS)): every operand is batch-leading
    devices: int


_SHARD_CACHE: dict[tuple[str, int], _Fanout] = {}


def _shard_min() -> int:
    """Bucketed-batch floor for multi-device fan-out; merged plane batches
    at/above it shard over the local mesh (parallel/sharding.py). High by
    default: below ~thousands of lanes one chip is faster than paying the
    all_gather + an extra compiled program."""
    try:
        return int(os.environ.get("FISCO_DEVICE_SHARD_MIN", "4096"))
    except ValueError:
        return 4096


def mesh_devices(bb: int) -> int:
    """The rule of the fan-out: how many devices a bucketed batch of `bb`
    lanes goes out over. Every local device where there is more than one,
    `bb` clears :func:`_shard_min` and the mesh divides it; otherwise 1, the
    single-chip jit, without a word (a caller that must know which it got
    reads ``fisco_device_mesh_calls_total``)."""
    ndev = len(jax.devices())
    if ndev <= 1 or bb < max(_shard_min(), ndev) or bb % ndev:
        return 1
    return ndev


def _maybe_sharded_step(body: _Body, bb: int) -> _Fanout | None:
    """The cached sharded admission program when :func:`mesh_devices` sends
    the bucketed batch `bb` out over the mesh; None otherwise (single-chip
    jit). Nothing is caught here: a mesh or program that fails
    raises into the caller's device leg, where the dispatch seam counts the
    failure, answers from the host loop and feeds the breaker —
    fan-out stays an optimization, never a liveness dependency, without
    hiding that it broke."""
    ndev = mesh_devices(bb)
    if ndev == 1:
        return None
    fanout = _SHARD_CACHE.get((body.op, ndev))
    if fanout is None:
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.sharding import DATA_AXIS, make_mesh, sharded_admission_packed

        mesh = make_mesh(ndev)
        fanout = _SHARD_CACHE[body.op, ndev] = _Fanout(
            sharded_admission_packed(mesh, body=body.packed),
            NamedSharding(mesh, PartitionSpec(DATA_AXIS)),
            ndev,
        )
    return fanout


def _admit_batch_device(
    payloads, sigs, allow_shard: bool = False, body: _Body = _SECP
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """The fused device program of `body` (hash → recover or verify →
    address), one result transfer. `allow_shard=True` (the seam's device leg)
    fans the bucketed batch out over the local device mesh when it clears
    _shard_min: the operands are then placed on the mesh as a phase of their
    own (``place``), the span says how wide the mesh was, and the call counts
    as a mesh call once its answer is on the host."""
    from ..observability.device import LEDGER, device_span
    from ..ops.hash_common import bucket_batch

    bsz = len(payloads)
    payloads = list(payloads)
    # the shape the program compiles for, known before any marshalling: the
    # padders bucket the batch dim (empty-message pad rows) and the
    # message-block dim; the signature operands follow the blocks tensor's
    # bucket
    bb = bucket_batch(max(bsz, 1))
    mblocks = bucket_batch(body.mblocks(max(map(len, payloads), default=0)))
    fanout = _maybe_sharded_step(body, bb) if allow_shard else None
    op, step = (
        (body.op, body.step) if fanout is None
        else (body.op + "_sharded", fanout.step)
    )
    with device_span(op, bsz, shape_key=(bb, mblocks)) as sp:
        with sp.phase("marshal"):
            operands = body.marshal(payloads, np.asarray(sigs, dtype=np.uint8), bb)
        # what the program does with a device's lanes is a function of their
        # number alone: known here without asking the program
        lanes = bb // (fanout.devices if fanout is not None else 1)
        plan = body.plan(lanes)
        sp.plan(plan.tiles(lanes), plan.tile)
        if fanout is not None:
            sp.set(devices=fanout.devices, lanes_per_device=lanes)
            with sp.phase("place"):  # one shard of every operand to its device
                operands = jax.block_until_ready(
                    jax.device_put(operands, fanout.sharding)
                )
        with sp.phase("enqueue"):  # a shape's first call traces + compiles here
            dev = step(*operands)
        with sp.phase("sync"):  # waits for the device, brings the result over
            packed = np.asarray(dev)
        if fanout is not None:
            LEDGER.note_mesh_call(body.op, fanout.devices, lanes)
        with sp.phase("unpack"):
            packed = packed[:bsz]
            return (
                packed[:, :20],
                packed[:, 20] != 0,
                packed[:, 21:85],
                packed[:, 85:117],
            )


def admit_batch(
    payloads, sigs, suite=None
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Host API: list[bytes] signed payloads + [B, L] signatures ->
    (senders [B, 20] uint8, ok bool[B], pubkeys [B, 64] uint8,
    tx hashes [B, 32] uint8). One device program, ONE result transfer —
    or the native host loop when that wins (small batch / CPU-only backend;
    device/dispatch.py holds the policy).

    `suite` (a crypto.suite.CryptoSuite) picks the body: 65-byte r‖s‖v under
    secp256k1 + keccak256, the default; 128-byte r‖s‖pub under SM2 + SM3. A
    suite without a fused body, or signatures of another width than the
    suite's, raise ValueError (``CryptoSuite.fused_admission`` answers None
    for the former instead).

    Routed through the shared DevicePlane: concurrent callers' batches
    (txpool RPC batches, consensus proposal re-verification, sync imports)
    coalesce into one policy decision and one program, shapes ride the bucket
    ladder, and oversized merged batches fan out over the device mesh.
    FISCO_FORCE_DEVICE_ADMISSION=1 pins the device program (tests use it to
    cover the device path on CPU hosts)."""
    body = _body_of(suite)
    if body is None:
        raise ValueError("admit_batch: this suite has no fused admission")
    bsz = len(payloads)
    sigs_arr = np.asarray(sigs, dtype=np.uint8)
    if bsz and sigs_arr.shape[-1] != body.sig_len:
        raise ValueError(
            f"admit_batch: {sigs_arr.shape[-1]}-byte signatures under a suite "
            f"that signs {body.sig_len} bytes"
        )
    return dispatch(body, (list(payloads), sigs_arr), bsz)


# -- progaudit shape spec: M=2 message-block dim (the short-payload bucket
# the flood pads to); both the raw core and the packed wrapper audit, and the
# SM body's packed wrapper.
PROGSPEC = {
    "admission_core": {
        "bucket": 256,
        "inputs": lambda b: [
            ((b, 2, 17, 2), "uint32"), ((b,), "int32"),
            ((b, 16), "uint32"), ((b, 16), "uint32"), ((b,), "int32"),
        ],
    },
    "_admission_packed": {
        "bucket": 256,
        "inputs": lambda b: [
            ((b, 2, 17, 2), "uint32"), ((b,), "int32"),
            ((b, 16), "uint32"), ((b, 16), "uint32"), ((b,), "int32"),
        ],
    },
    "_sm_admission_packed": {
        "bucket": 256,
        "inputs": lambda b: [((b, 2, 16), "uint32"), ((b,), "int32")]
        + [((b, 16), "uint32")] * 4,
    },
}

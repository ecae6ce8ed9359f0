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
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import keccak, secp256k1
from ..ops.address import sender_address_device
from ..ops.bigint import bytes_be_to_limbs, digest_words_le_to_limbs
from ..ops.hash_common import pad_keccak, pad_rows


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


def _admission_packed(blocks, nblocks, r, s, v):
    return pack_admission_device(*admission_core(blocks, nblocks, r, s, v))


admission_step_packed = jax.jit(_admission_packed)


def _admit_batch_native(payloads, sigs65):
    """Host-loop admission through the native C core (keccak → recover →
    address), bit-identical to the device program on valid lanes
    (tests/test_admission.py pins it). None when the native library is
    unavailable. ~0.3ms/sig — beats the DEVICE path outright when the jax
    backend is CPU XLA, and a device dispatch's fixed cost for small
    batches (crypto.suite._SMALL_BATCH)."""
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


# -- multi-device fan-out -----------------------------------------------------

_SHARD_CACHE: dict[int, object] = {}


def _shard_min() -> int:
    """Bucketed-batch floor for multi-device fan-out; merged plane batches
    at/above it shard over the local mesh (parallel/sharding.py). High by
    default: below ~thousands of lanes one chip is faster than paying the
    all_gather + an extra compiled program."""
    try:
        return int(os.environ.get("FISCO_DEVICE_SHARD_MIN", "4096"))
    except ValueError:
        return 4096


def _maybe_sharded_step(bb: int):
    """The cached sharded admission program when the bucketed batch `bb`
    clears the fan-out threshold on a multi-device mesh; None otherwise
    (single-chip jit). Nothing is caught here: a mesh or program that fails
    raises into the caller's device leg, where crypto.suite._device_or_host
    counts the failure, answers from the host loop and feeds the breaker —
    fan-out stays an optimization, never a liveness dependency, without
    hiding that it broke."""
    ndev = len(jax.devices())
    if ndev <= 1 or bb < max(_shard_min(), ndev) or bb % ndev:
        return None
    step = _SHARD_CACHE.get(ndev)
    if step is None:
        from ..parallel.sharding import make_mesh, sharded_admission_packed

        step = _SHARD_CACHE[ndev] = sharded_admission_packed(make_mesh(ndev))
    return step


def _admit_batch_device(
    payloads, sigs65, allow_shard: bool = False
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """The fused device program (keccak → recover → address), one result
    transfer. `allow_shard=True` (plane dispatches only) fans the bucketed
    batch out over the local device mesh when it clears _shard_min."""
    from ..observability.device import device_span
    from ..ops.hash_common import bucket_batch

    bsz = len(payloads)
    payloads = list(payloads)
    # the shape the program compiles for, known before any marshalling:
    # pad_keccak buckets the batch dim (empty-message pad rows) and the
    # message-block dim; r/s/v follow the blocks tensor's bucket
    bb = bucket_batch(max(bsz, 1))
    mblocks = bucket_batch(max(map(len, payloads), default=0) // 136 + 1)
    step = _maybe_sharded_step(bb) if allow_shard else None
    op = "admission" if step is None else "admission_sharded"
    if step is None:
        step = admission_step_packed
    with device_span(op, bsz, shape_key=(bb, mblocks)) as sp:
        with sp.phase("marshal"):
            blocks, nblocks = pad_keccak(payloads)
            sigs65 = np.asarray(sigs65, dtype=np.uint8)
            r = pad_rows(bytes_be_to_limbs(sigs65[:, :32]), bb)
            s = pad_rows(bytes_be_to_limbs(sigs65[:, 32:64]), bb)
            v = pad_rows(sigs65[:, 64].astype(np.int32), bb)
        with sp.phase("enqueue"):  # a shape's first call traces + compiles here
            dev = step(blocks, nblocks, r, s, v)
        with sp.phase("sync"):  # waits for the device, brings the result over
            packed = np.asarray(dev)
        with sp.phase("unpack"):
            packed = packed[:bsz]
            return (
                packed[:, :20],
                packed[:, 20] != 0,
                packed[:, 21:85],
                packed[:, 85:117],
            )


def _try_native(payloads, sigs65):
    """The native-host-loop leg when policy picks it; None to use device."""
    from ..observability.device import device_span
    from .suite import use_native_batch

    if os.environ.get("FISCO_FORCE_DEVICE_ADMISSION"):
        return None
    if not use_native_batch(len(payloads)):
        return None
    # native host loop — shape_key pinned so it never reads as
    # a compile; the op label keeps the dispatch split visible
    with device_span("admission_native", len(payloads), shape_key="native"):
        return _admit_batch_native(payloads, np.asarray(sigs65, dtype=np.uint8))


def _admit_direct(payloads, sigs65):
    """Pre-plane per-caller dispatch (the FISCO_DEVICE_PLANE=0 path):
    native-vs-device decided for THIS call alone — no coalescing, no
    fan-out, no breaker."""
    from .suite import _note_dispatch_path

    out = _try_native(payloads, sigs65)
    if out is not None:
        _note_dispatch_path("admission", "native")
        return out
    _note_dispatch_path("admission", "device")
    return _admit_batch_device(payloads, sigs65, allow_shard=False)


def _admit_merged(payloads, sigs65):
    """Plane-executor body: the same native-vs-device policy applied to the
    MERGED batch, with multi-device fan-out allowed and the device leg under
    the resilience breaker (host-loop fallback keeps admission serving when
    the device plane is degraded)."""
    from .suite import _device_or_host, _note_dispatch_path

    out = _try_native(payloads, sigs65)
    if out is not None:
        _note_dispatch_path("admission", "native")
        return out

    def _host(p, s):
        host_out = _admit_batch_native(p, np.asarray(s, dtype=np.uint8))
        if host_out is None:
            raise RuntimeError("native admission unavailable for host fallback")
        return host_out

    return _device_or_host(
        "admission",
        lambda p, s: _admit_batch_device(p, s, allow_shard=True),
        _host,
        payloads,
        sigs65,
    )


def _admission_plane_exec(reqs):
    """DevicePlane executor: merge every queued admission request (txpool
    RPC batches, consensus proposal re-verification, sync imports) into one
    policy decision + one device program, then slice results per request."""
    payloads: list[bytes] = []
    rows = []
    for r in reqs:
        payloads.extend(r.payload[0])
        rows.append(r.payload[1])
    sigs65 = np.concatenate(rows, axis=0)
    senders, ok, pubs, digests = _admit_merged(payloads, sigs65)
    senders, ok = np.asarray(senders), np.asarray(ok)
    pubs, digests = np.asarray(pubs), np.asarray(digests)
    out, lo = [], 0
    for r in reqs:
        hi = lo + r.n
        out.append((senders[lo:hi], ok[lo:hi], pubs[lo:hi], digests[lo:hi]))
        lo = hi
    return out


def admit_batch(
    payloads, sigs65
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Host API: list[bytes] signed payloads + [B, 65] r‖s‖v signatures ->
    (senders [B, 20] uint8, ok bool[B], pubkeys [B, 64] uint8,
    tx hashes [B, 32] uint8). One device program, ONE result transfer —
    or the native host loop when that wins (small batch / CPU-only backend;
    crypto.suite.use_native_batch holds the policy).

    Routed through the shared DevicePlane: concurrent callers' batches
    coalesce into one program, shapes ride the bucket ladder, and oversized
    merged batches fan out over the device mesh. ``FISCO_DEVICE_PLANE=0``
    restores the per-caller direct dispatch exactly.
    FISCO_FORCE_DEVICE_ADMISSION=1 pins the device program (tests use it to
    cover the device path on CPU hosts)."""
    from ..device.plane import get_plane, plane_route, plane_wait

    bsz = len(payloads)
    if plane_route() and bsz:
        sigs_arr = np.asarray(sigs65, dtype=np.uint8)
        return plane_wait(get_plane().submit(
            "admission", (list(payloads), sigs_arr), bsz, _admission_plane_exec
        ))
    return _admit_direct(payloads, sigs65)


# -- progaudit shape spec: M=2 message-block dim (the short-payload bucket
# the flood pads to); both the raw core and the packed wrapper audit.
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
}

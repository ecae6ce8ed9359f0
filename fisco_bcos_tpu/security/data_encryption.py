"""DataEncryption + the encrypted storage wrapper.

Reference: bcos-security/DataEncryption.h:35-55 (`encrypt`/`decrypt` over the
configured dataKey; applied to the node key file and every storage value —
RocksDBStorage's enableDBEncryption path), with KeyCenter.cpp's external key
service replaced by the local dataKey seam (`storage_security.data_key` in
config.ini).

``EncryptedStorage`` wraps any TransactionalStorage: entry payloads are
encrypted at rest (keys stay plaintext, exactly like the reference's rocksdb
values-only encryption), transparently for every reader — ledger, state,
txpool persistence, consensus state.
"""

from __future__ import annotations

from typing import Iterator

from ..crypto.encrypt import make_encryption
from ..storage.entry import Entry, EntryStatus
from ..storage.interfaces import (
    TransactionalStorage,
    TraversableStorage,
    TwoPCParams,
)


class DataEncryption:
    """dataKey-bound encrypt/decrypt (DataEncryption.cpp)."""

    def __init__(self, data_key: bytes, sm_crypto: bool = False):
        if not data_key:
            raise ValueError("storage_security requires a non-empty data_key")
        self._cipher = make_encryption(data_key, sm_crypto)

    def encrypt(self, data: bytes) -> bytes:
        return self._cipher.encrypt(data)

    def decrypt(self, data: bytes) -> bytes:
        return self._cipher.decrypt(data)


class _EncryptingView(TraversableStorage):
    """Traversal adapter handing the backend encrypted entries during 2PC."""

    def __init__(self, inner: TraversableStorage, enc: DataEncryption):
        self._inner = inner
        self._enc = enc

    def traverse(self) -> Iterator[tuple[str, bytes, Entry]]:
        for table, key, entry in self._inner.traverse():
            yield table, key, _seal(entry, self._enc)


def _seal(entry: Entry, enc: DataEncryption) -> Entry:
    if entry.deleted:
        return entry
    return Entry({"enc": enc.encrypt(entry.encode())}, status=entry.status)


def _open(entry: Entry | None, enc: DataEncryption) -> Entry | None:
    if entry is None or entry.deleted:
        return entry
    blob = entry.fields.get("enc")
    if blob is None:
        return entry  # pre-encryption row (mixed-mode migration)
    return Entry.decode(enc.decrypt(blob))


class EncryptedStorage(TransactionalStorage):
    def __init__(self, inner: TransactionalStorage, enc: DataEncryption):
        self.inner = inner
        self.enc = enc

    def get_row(self, table: str, key: bytes) -> Entry | None:
        return _open(self.inner.get_row(table, key), self.enc)

    def set_row(self, table: str, key: bytes, entry: Entry) -> None:
        self.inner.set_row(table, key, _seal(entry, self.enc))

    def set_rows(self, table: str, items) -> None:
        self.inner.set_rows(
            table, [(k, _seal(e, self.enc)) for k, e in items]
        )

    def get_primary_keys(self, table: str) -> list[bytes]:
        return self.inner.get_primary_keys(table)

    def traverse(self) -> Iterator[tuple[str, bytes, Entry]]:
        for table, key, entry in self.inner.traverse():
            opened = _open(entry, self.enc)
            if opened is not None:
                yield table, key, opened

    # -- 2PC: encrypt the staged write-set on its way down -------------------

    def prepare(self, params: TwoPCParams, writes: TraversableStorage) -> dict[str, int] | None:
        return self.inner.prepare(params, _EncryptingView(writes, self.enc))

    def commit(self, params: TwoPCParams) -> None:
        self.inner.commit(params)

    def rollback(self, params: TwoPCParams) -> None:
        self.inner.rollback(params)

    def pending_numbers(self) -> list[int]:
        return self.inner.pending_numbers()


    def close(self) -> None:
        close = getattr(self.inner, "close", None)
        if close is not None:
            close()

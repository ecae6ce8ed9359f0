"""Contract auth governance — method ACLs and contract freezing.

Reference: bcos-executor/src/precompiled/extension/
{AuthManagerPrecompiled.cpp (0x1005), ContractAuthMgrPrecompiled.cpp
(0x10002)}: per-(contract, selector) auth types (white/black list), per-
account open/close, contract freeze/unfreeze, and an admin per contract.
This implementation keeps the governed surface (setMethodAuthType /
openMethodAuth / closeMethodAuth / checkMethodAuth / setContractStatus /
contractAvailable / getAdmin-resetAdmin) over an ``s_contract_auth`` table;
the reference's committee/proposal layer (AuthCommittee Solidity contracts)
is out of scope — admin changes here are direct admin calls.

Auth types (ContractAuthMgrPrecompiled.h): 0 = no ACL, 1 = white list
(only listed accounts may call), 2 = black list (listed accounts may not).
"""

from __future__ import annotations

import json

from ...storage.entry import Entry
from .base import (
    Precompiled,
    PrecompiledCallContext,
    PrecompiledError,
    PrecompiledResult,
)

AUTH_TABLE = "s_contract_auth"

WHITE_LIST = 1
BLACK_LIST = 2


def _key(contract: bytes, selector: bytes) -> bytes:
    return contract + b":" + selector


def _meta_key(contract: bytes) -> bytes:
    return contract + b":#meta"


def _load(ctx, key: bytes) -> dict:
    e = ctx.storage.get_row(AUTH_TABLE, key)
    if e is None or not e.get():
        return {}
    return json.loads(e.get().decode())


def _store(ctx, key: bytes, obj: dict) -> None:
    ctx.storage.set_row(
        AUTH_TABLE, key, Entry({"value": json.dumps(obj).encode()})
    )


def _addr(a: str) -> bytes:
    raw = bytes.fromhex(a[2:] if a.startswith("0x") else a)
    if len(raw) != 20:
        raise PrecompiledError(f"bad address: {a!r}")
    return raw


# ---------------------------------------------------------------------------
# Enforcement helpers — called by the EXECUTOR, not just the RPC surface
# (the reference's TransactionExecutive consults ContractAuthMgr before
# running a frame; governance that is recorded but unenforced is theater)
# ---------------------------------------------------------------------------


def _load_raw(storage, key: bytes) -> dict:
    e = storage.get_row(AUTH_TABLE, key)
    if e is None or not e.get():
        return {}
    return json.loads(e.get().decode())


def meta_row(contract: bytes) -> tuple[str, bytes]:
    """The row ``is_frozen`` reads, as (table, key)."""
    return AUTH_TABLE, _meta_key(contract)


def acl_row(contract: bytes, selector: bytes) -> tuple[str, bytes]:
    """The row ``acl_allows`` reads, as (table, key)."""
    return AUTH_TABLE, _key(contract, selector[:4])


def bind_admin(storage, contract: bytes, admin: bytes) -> None:
    """Deploy-time admin binding (AuthManager binds the deployer): first
    writer wins; an existing admin is never overwritten."""
    key = _meta_key(contract)
    meta = _load_raw(storage, key)
    if meta.get("admin"):
        return
    meta["admin"] = "0x" + admin.hex()
    storage.set_row(AUTH_TABLE, key, Entry({"value": json.dumps(meta).encode()}))


def is_frozen(storage, contract: bytes) -> bool:
    return bool(_load_raw(storage, _meta_key(contract)).get("frozen", False))


def acl_allows(storage, contract: bytes, selector: bytes, account: bytes) -> bool:
    acl = _load_raw(storage, _key(contract, selector[:4]))
    t = acl.get("type", 0)
    if t == 0:
        return True
    if t == WHITE_LIST:
        return acl.get("white", {}).get("0x" + account.hex()) is True
    return acl.get("black", {}).get("0x" + account.hex()) is not True


class ContractAuthPrecompiled(Precompiled):
    """The governed ACL surface shared by AuthManager/ContractAuthMgr."""

    def setup(self, codec):
        self.register(codec, "setMethodAuthType(string,bytes4,uint8)", self._set_type)
        self.register(codec, "openMethodAuth(string,bytes4,string)", self._open)
        self.register(codec, "closeMethodAuth(string,bytes4,string)", self._close)
        self.register(codec, "checkMethodAuth(string,bytes4,string)", self._check)
        self.register(codec, "setContractStatus(string,bool)", self._set_status)
        self.register(codec, "contractAvailable(string)", self._available)
        self.register(codec, "getAdmin(string)", self._get_admin)
        self.register(codec, "resetAdmin(string,string)", self._reset_admin)
        self.register(codec, "initAdmin(string,string)", self._init_admin)

    # -- admin ----------------------------------------------------------------

    def _admin_of(self, ctx, contract: bytes) -> bytes:
        meta = _load(ctx, _meta_key(contract))
        return _addr(meta["admin"]) if meta.get("admin") else b""

    def _require_admin(self, ctx, contract: bytes) -> None:
        admin = self._admin_of(ctx, contract)
        if admin and ctx.sender != admin:
            raise PrecompiledError("sender is not the contract admin")

    def _init_admin(self, ctx: PrecompiledCallContext, contract: str, admin: str):
        """First-touch admin binding (the reference binds the deployer via
        AuthManager at deploy time)."""
        c = _addr(contract)
        meta = _load(ctx, _meta_key(c))
        if meta.get("admin"):
            raise PrecompiledError("admin already set")
        meta["admin"] = "0x" + _addr(admin).hex()
        _store(ctx, _meta_key(c), meta)
        return PrecompiledResult(output=ctx.codec.encode_output(["int256"], 0))

    def _get_admin(self, ctx: PrecompiledCallContext, contract: str):
        admin = self._admin_of(ctx, _addr(contract))
        return PrecompiledResult(
            output=ctx.codec.encode_output(["address"], admin or b"\x00" * 20)
        )

    def _reset_admin(self, ctx: PrecompiledCallContext, contract: str, admin: str):
        c = _addr(contract)
        self._require_admin(ctx, c)
        meta = _load(ctx, _meta_key(c))
        meta["admin"] = "0x" + _addr(admin).hex()
        _store(ctx, _meta_key(c), meta)
        return PrecompiledResult(output=ctx.codec.encode_output(["int256"], 0))

    # -- method ACLs -----------------------------------------------------------

    def _set_type(
        self, ctx: PrecompiledCallContext, contract: str, selector: bytes, auth_type: int
    ):
        if auth_type not in (0, WHITE_LIST, BLACK_LIST):
            raise PrecompiledError(f"bad auth type {auth_type}")
        c = _addr(contract)
        self._require_admin(ctx, c)
        k = _key(c, bytes(selector[:4]))
        acl = _load(ctx, k)
        acl["type"] = auth_type
        _store(ctx, k, acl)
        return PrecompiledResult(output=ctx.codec.encode_output(["int256"], 0))

    def _toggle(self, ctx, contract: str, selector: bytes, account: str, opened: bool):
        c = _addr(contract)
        self._require_admin(ctx, c)
        k = _key(c, bytes(selector[:4]))
        acl = _load(ctx, k)
        t = acl.get("type")
        if not t:
            raise PrecompiledError("method has no auth type set")
        # separate white/black account tables, like the reference's
        # method_auth_white / method_auth_black rows — switching the auth
        # type must not leak the other list's entries
        bucket = "white" if t == WHITE_LIST else "black"
        acl.setdefault(bucket, {})["0x" + _addr(account).hex()] = opened
        _store(ctx, k, acl)
        return PrecompiledResult(output=ctx.codec.encode_output(["int256"], 0))

    def _open(self, ctx, contract: str, selector: bytes, account: str):
        return self._toggle(ctx, contract, selector, account, True)

    def _close(self, ctx, contract: str, selector: bytes, account: str):
        return self._toggle(ctx, contract, selector, account, False)

    def _check_impl(self, ctx, contract: bytes, selector: bytes, account: bytes) -> bool:
        return acl_allows(ctx.storage, contract, selector, account)

    def _check(self, ctx: PrecompiledCallContext, contract: str, selector: bytes, account: str):
        ok = self._check_impl(ctx, _addr(contract), bytes(selector), _addr(account))
        return PrecompiledResult(output=ctx.codec.encode_output(["bool"], ok))

    # -- contract status (freeze/unfreeze) ------------------------------------

    def _set_status(self, ctx: PrecompiledCallContext, contract: str, is_frozen: bool):
        c = _addr(contract)
        self._require_admin(ctx, c)
        meta = _load(ctx, _meta_key(c))
        meta["frozen"] = bool(is_frozen)
        _store(ctx, _meta_key(c), meta)
        return PrecompiledResult(output=ctx.codec.encode_output(["int256"], 0))

    def _available(self, ctx: PrecompiledCallContext, contract: str):
        return PrecompiledResult(
            output=ctx.codec.encode_output(
                ["bool"], not is_frozen(ctx.storage, _addr(contract))
            )
        )

"""Benchmark contracts: DagTransfer, SmallBank, CpuHeavy.

These are the reference's own load generators
(bcos-executor/src/precompiled/extension/{DagTransferPrecompiled,
SmallBankPrecompiled, CpuHeavyPrecompiled}.cpp) behind the headline TPS
numbers. DagTransfer/SmallBank declare per-user conflict keys, which is what
makes blocks of them DAG-parallel (and, here, vectorizable per DAG level).
"""

from __future__ import annotations

from functools import lru_cache

from ...codec.abi import abi_encode, coder_for
from ...storage.entry import Entry
from .base import (
    Precompiled,
    PrecompiledCallContext,
    PrecompiledError,
    PrecompiledResult,
)

_U256_MAX = (1 << 256) - 1

DAG_TRANSFER_TABLE = "dag_transfer"


@lru_cache(maxsize=16)
def _code_word(code: int) -> bytes:
    """A return code as its ``uint256`` output: a handful of codes, each
    encoded once."""
    return abi_encode(["uint256"], [code])


class DagTransferPrecompiled(Precompiled):
    """userAdd/userSave/userDraw/userBalance/userTransfer over a user→balance
    table (DagTransferPrecompiled.cpp:37-48)."""

    parallel = True

    def setup(self, codec):
        self.register(codec, "userAdd(string,uint256)", self._add)
        self.register(codec, "userSave(string,uint256)", self._save)
        self.register(codec, "userDraw(string,uint256)", self._draw)
        self.register(codec, "userBalance(string)", self._balance)
        self.register(codec, "userTransfer(string,string,uint256)", self._transfer)
        # selector -> (its compiled decoder, how many leading user names)
        self._crit_sigs = {
            codec.selector(sig): (coder_for(types).decode, n_users)
            for sig, types, n_users in (
                ("userAdd(string,uint256)", ("string", "uint256"), 1),
                ("userSave(string,uint256)", ("string", "uint256"), 1),
                ("userDraw(string,uint256)", ("string", "uint256"), 1),
                ("userBalance(string)", ("string",), 1),
                ("userTransfer(string,string,uint256)", ("string", "string", "uint256"), 2),
            )
        }

    def criticals(self, codec, data: bytes):
        if not self._methods:
            self.setup(codec)
        entry = self._crit_sigs.get(data[:4])
        if entry is None:
            return None
        decode, n_users = entry
        # conflict keys = the user-name string args (reference: conflict
        # fields annotated on each parallel method)
        try:
            vals = decode(data[4:])
        except ValueError:
            return None
        return [v.encode() for v in vals[:n_users]]

    # -- state helpers ------------------------------------------------------

    @staticmethod
    def _get_balance(ctx, user: str) -> int | None:
        e = ctx.storage.get_row(DAG_TRANSFER_TABLE, user.encode())
        return int(e.get("balance").decode()) if e is not None else None

    @staticmethod
    def _set_balance(ctx, user: str, balance: int) -> None:
        ctx.storage.set_row(
            DAG_TRANSFER_TABLE,
            user.encode(),
            Entry().set("balance", str(balance).encode()),
        )

    @staticmethod
    def _ret(ctx, code: int) -> PrecompiledResult:
        return PrecompiledResult(output=_code_word(code))

    # -- methods (return codes follow the reference: 0 = ok) ----------------

    def _add(self, ctx: PrecompiledCallContext, user: str, balance: int):
        if not user:
            return self._ret(ctx, 1)
        if self._get_balance(ctx, user) is not None:
            return self._ret(ctx, 2)  # already exists
        self._set_balance(ctx, user, balance)
        return self._ret(ctx, 0)

    def _save(self, ctx, user: str, amount: int):
        if not user or amount == 0:
            return self._ret(ctx, 1)
        bal = self._get_balance(ctx, user)
        bal = 0 if bal is None else bal
        if bal + amount > _U256_MAX:
            return self._ret(ctx, 3)  # overflow
        self._set_balance(ctx, user, bal + amount)
        return self._ret(ctx, 0)

    def _draw(self, ctx, user: str, amount: int):
        if not user or amount == 0:
            return self._ret(ctx, 1)
        bal = self._get_balance(ctx, user)
        if bal is None:
            return self._ret(ctx, 2)
        if bal < amount:
            return self._ret(ctx, 4)  # insufficient
        self._set_balance(ctx, user, bal - amount)
        return self._ret(ctx, 0)

    def _balance(self, ctx, user: str):
        bal = self._get_balance(ctx, user)
        ok = 0 if bal is not None else 2
        return PrecompiledResult(
            output=ctx.codec.encode_output(["uint256", "uint256"], ok, bal or 0)
        )

    def _transfer(self, ctx, user_a: str, user_b: str, amount: int):
        if not user_a or not user_b:
            return self._ret(ctx, 1)
        bal_a = self._get_balance(ctx, user_a)
        if bal_a is None:
            return self._ret(ctx, 2)
        if bal_a < amount:
            return self._ret(ctx, 4)
        bal_b = self._get_balance(ctx, user_b)
        if bal_b is None:
            return self._ret(ctx, 3)
        if user_a == user_b:
            return self._ret(ctx, 0)
        if bal_b + amount > _U256_MAX:
            return self._ret(ctx, 5)
        self._set_balance(ctx, user_a, bal_a - amount)
        self._set_balance(ctx, user_b, bal_b + amount)
        return self._ret(ctx, 0)


SMALLBANK_SAVING = "smallbank_saving"
SMALLBANK_CHECKING = "smallbank_checking"


class SmallBankPrecompiled(Precompiled):
    """SmallBank OLTP benchmark (SmallBankPrecompiled.cpp): per-user saving +
    checking balances."""

    parallel = True

    def setup(self, codec):
        self.register(codec, "updateBalance(string,uint256)", self._update_balance)
        self.register(codec, "updateSaving(string,uint256)", self._update_saving)
        self.register(codec, "sendPayment(string,string,uint256)", self._send_payment)
        self.register(codec, "writeCheck(string,uint256)", self._write_check)
        self.register(codec, "amalgamate(string,string)", self._amalgamate)
        self.register(codec, "getBalance(string)", self._get_balance_m)
        self._crit_counts = {
            codec.selector("updateBalance(string,uint256)"): 1,
            codec.selector("updateSaving(string,uint256)"): 1,
            codec.selector("sendPayment(string,string,uint256)"): 2,
            codec.selector("writeCheck(string,uint256)"): 1,
            codec.selector("amalgamate(string,string)"): 2,
            codec.selector("getBalance(string)"): 1,
        }

    def criticals(self, codec, data: bytes):
        if not self._methods:
            self.setup(codec)
        n = self._crit_counts.get(data[:4])
        if n is None:
            return None
        try:
            vals = coder_for(("string",) * n).decode(data[4:])
        except ValueError:
            return None
        return [v.encode() for v in vals]

    @staticmethod
    def _get(ctx, table: str, user: str) -> int:
        e = ctx.storage.get_row(table, user.encode())
        return int(e.get("balance").decode()) if e is not None else 0

    @staticmethod
    def _set(ctx, table: str, user: str, v: int) -> None:
        if v < 0:
            raise PrecompiledError("smallbank: negative balance")
        ctx.storage.set_row(table, user.encode(), Entry().set("balance", str(v).encode()))

    @staticmethod
    def _ok(ctx) -> PrecompiledResult:
        return PrecompiledResult(output=ctx.codec.encode_output(["uint256"], 0))

    def _update_balance(self, ctx, user: str, v: int):
        self._set(ctx, SMALLBANK_CHECKING, user, v)
        return self._ok(ctx)

    def _update_saving(self, ctx, user: str, v: int):
        self._set(ctx, SMALLBANK_SAVING, user, v)
        return self._ok(ctx)

    def _send_payment(self, ctx, a: str, b: str, amount: int):
        bal_a = self._get(ctx, SMALLBANK_CHECKING, a)
        if bal_a < amount:
            raise PrecompiledError("smallbank: insufficient checking balance")
        self._set(ctx, SMALLBANK_CHECKING, a, bal_a - amount)
        self._set(ctx, SMALLBANK_CHECKING, b, self._get(ctx, SMALLBANK_CHECKING, b) + amount)
        return self._ok(ctx)

    def _write_check(self, ctx, user: str, amount: int):
        bal = self._get(ctx, SMALLBANK_CHECKING, user)
        if bal < amount:
            raise PrecompiledError("smallbank: insufficient funds for check")
        self._set(ctx, SMALLBANK_CHECKING, user, bal - amount)
        return self._ok(ctx)

    def _amalgamate(self, ctx, a: str, b: str):
        sav = self._get(ctx, SMALLBANK_SAVING, a)
        self._set(ctx, SMALLBANK_SAVING, a, 0)
        self._set(ctx, SMALLBANK_CHECKING, b, self._get(ctx, SMALLBANK_CHECKING, b) + sav)
        return self._ok(ctx)

    def _get_balance_m(self, ctx, user: str):
        total = self._get(ctx, SMALLBANK_SAVING, user) + self._get(
            ctx, SMALLBANK_CHECKING, user
        )
        return PrecompiledResult(output=ctx.codec.encode_output(["uint256"], total))


class CpuHeavyPrecompiled(Precompiled):
    """CPU-bound benchmark: sort(size, seed) (CpuHeavyPrecompiled.cpp runs
    quicksort over a generated array; stateless)."""

    parallel = True

    def setup(self, codec):
        self.register(codec, "sort(uint256,uint256)", self._sort)

    def criticals(self, codec, data: bytes):
        if not self._methods:
            self.setup(codec)
        if data[:4] in self._methods:
            return []  # stateless: conflicts with nothing
        return None

    def _sort(self, ctx, size: int, seed: int):
        if size > 1_000_000:
            raise PrecompiledError("cpu_heavy: size too large")
        xs = []
        x = (seed or 1) & 0xFFFFFFFF
        for _ in range(size):
            x = (1103515245 * x + 12345) & 0x7FFFFFFF  # glibc LCG
            xs.append(x)
        xs.sort()
        checksum = xs[size // 2] if size else 0
        return PrecompiledResult(
            output=ctx.codec.encode_output(["uint256"], checksum),
            gas_used=16_000 + 10 * size,
        )

"""Solidity ABI codec — the full static/dynamic type algebra.

Reference: bcos-codec/abi/ContractABICodec.* (used by every precompile for
input parsing and output encoding, e.g.
bcos-executor/src/precompiled/extension/DagTransferPrecompiled.cpp:44-64's
name2Selector table). Covers the reference codec's whole surface: elementary
types (uintN/intN, address, bool, bytesN, bytes, string), fixed-size arrays
``T[k]``, dynamic arrays ``T[]``, nested arrays, and tuples ``(T1,T2,...)``
with arbitrary nesting — head/tail layout per the Solidity ABI spec, with
strict decode (out-of-range offsets and truncated data raise, they don't
yield empty values). Function selector = first 4 bytes of
hash("name(type,...)"), where the hash is the suite hash (keccak256, or SM3
on SM chains — matching the reference's getFuncSelector,
precompiled/common/Utilities.cpp).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Any

_WORD = 32


# ---------------------------------------------------------------------------
# Type grammar
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AbiType:
    """Parsed ABI type. `base` is one of uint/int/address/bool/fbytes/
    bytes/string/array/tuple; `bits` holds the uint/int width or the
    fixed-bytes byte count; arrays carry `elem` and `length` (-1 = dynamic);
    tuples carry `components`."""

    base: str
    bits: int = 0
    length: int = -1
    elem: "AbiType | None" = None
    components: tuple = ()

    @property
    def is_dynamic(self) -> bool:
        if self.base in ("bytes", "string"):
            return True
        if self.base == "array":
            return self.length < 0 or self.elem.is_dynamic
        if self.base == "tuple":
            return any(c.is_dynamic for c in self.components)
        return False

    @property
    def head_words(self) -> int:
        """Words this type occupies in its enclosing head block
        (1 for any dynamic type: the offset word)."""
        if self.is_dynamic:
            return 1
        if self.base == "array":
            return self.length * self.elem.head_words
        if self.base == "tuple":
            return sum(c.head_words for c in self.components)
        return 1


def split_toplevel(s: str, sep: str = ",") -> list[str]:
    """Split on `sep` at bracket depth 0 (tuple/array aware)."""
    parts: list[str] = []
    depth = 0
    cur: list[str] = []
    for ch in s:
        if ch in "([":
            depth += 1
        elif ch in ")]":
            depth -= 1
            if depth < 0:
                raise ValueError(f"unbalanced brackets in {s!r}")
        if ch == sep and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if depth != 0:
        raise ValueError(f"unbalanced brackets in {s!r}")
    parts.append("".join(cur))
    return [p.strip() for p in parts if p.strip()]


@lru_cache(maxsize=4096)
def parse_type(s: str) -> AbiType:
    # memoized: AbiType is frozen, and block execution parses the same few
    # signatures for every tx (a top host cost in the flood profile)
    s = s.strip()
    if not s:
        raise ValueError("empty type")
    if s.endswith("]"):
        i = s.rindex("[")
        inner = s[i + 1 : -1].strip()
        if inner:
            k = int(inner)
            if k < 0:
                raise ValueError(f"negative array length in {s!r}")
        else:
            k = -1
        return AbiType("array", length=k, elem=parse_type(s[:i]))
    if s.startswith("(") and s.endswith(")"):
        return AbiType(
            "tuple", components=tuple(parse_type(p) for p in split_toplevel(s[1:-1]))
        )
    if s in ("string", "bytes", "address", "bool"):
        return AbiType(s)
    if s.startswith("uint"):
        bits = int(s[4:]) if s[4:] else 256
        if not 8 <= bits <= 256 or bits % 8:
            raise ValueError(f"bad uint width {s!r}")
        return AbiType("uint", bits=bits)
    if s.startswith("int"):
        bits = int(s[3:]) if s[3:] else 256
        if not 8 <= bits <= 256 or bits % 8:
            raise ValueError(f"bad int width {s!r}")
        return AbiType("int", bits=bits)
    if s.startswith("bytes"):
        n = int(s[5:])
        if not 1 <= n <= 32:
            raise ValueError(f"bad fixed-bytes width {s!r}")
        return AbiType("fbytes", bits=n)
    raise ValueError(f"unsupported ABI type {s!r}")


# ---------------------------------------------------------------------------
# Encoding
# ---------------------------------------------------------------------------


def _pad_right(b: bytes) -> bytes:
    r = len(b) % _WORD
    return b + b"\x00" * (_WORD - r) if r else b


def _encode_static_word(t: AbiType, val: Any) -> bytes:
    if t.base == "uint" or t.base == "bool":
        v = int(val)
        if v < 0:
            raise ValueError(f"uint{t.bits or ''} cannot encode negative {v}")
        if t.base == "uint" and v >> t.bits:
            raise ValueError(f"uint{t.bits} overflow: {v}")
        return v.to_bytes(_WORD, "big")
    if t.base == "int":
        v = int(val)
        if not -(1 << (t.bits - 1)) <= v < (1 << (t.bits - 1)):
            raise ValueError(f"int{t.bits} overflow: {v}")
        return v.to_bytes(_WORD, "big", signed=True)
    if t.base == "address":
        if isinstance(val, str):
            b = bytes.fromhex(val[2:] if val[:2] in ("0x", "0X") else val)
        else:
            b = bytes(val)
        if len(b) != 20:
            raise ValueError("address must be 20 bytes")
        return b"\x00" * 12 + b
    if t.base == "fbytes":
        b = bytes(val)
        if len(b) > t.bits:
            raise ValueError(f"bytes{t.bits} overflow")
        return b.ljust(_WORD, b"\x00")
    raise ValueError(f"not a static word type: {t.base}")


def _encode_value(t: AbiType, val: Any) -> bytes:
    """Full encoding of one value — for dynamic types this is the tail."""
    if t.base == "string":
        raw = val.encode() if isinstance(val, str) else bytes(val)
        return len(raw).to_bytes(_WORD, "big") + _pad_right(raw)
    if t.base == "bytes":
        raw = bytes(val)
        return len(raw).to_bytes(_WORD, "big") + _pad_right(raw)
    if t.base == "array":
        vals = list(val)
        if t.length >= 0 and len(vals) != t.length:
            raise ValueError(
                f"fixed array expects {t.length} elements, got {len(vals)}"
            )
        body = _encode_sequence([t.elem] * len(vals), vals)
        if t.length < 0:
            return len(vals).to_bytes(_WORD, "big") + body
        return body
    if t.base == "tuple":
        vals = list(val)
        if len(vals) != len(t.components):
            raise ValueError(
                f"tuple expects {len(t.components)} fields, got {len(vals)}"
            )
        return _encode_sequence(list(t.components), vals)
    return _encode_static_word(t, val)


def _encode_sequence(types: list[AbiType], values: list[Any]) -> bytes:
    """Head/tail layout of a value sequence (top-level args, tuple fields,
    array elements all share this shape; offsets are relative to the
    sequence start)."""
    heads: list[bytes] = []
    tails: list[bytes] = []
    head_len = _WORD * sum(t.head_words for t in types)
    for t, v in zip(types, values):
        if t.is_dynamic:
            offset = head_len + sum(len(x) for x in tails)
            heads.append(offset.to_bytes(_WORD, "big"))
            tails.append(_encode_value(t, v))
        else:
            heads.append(_encode_value(t, v))
    return b"".join(heads) + b"".join(tails)


def abi_encode(types: list[str], values: list[Any]) -> bytes:
    """Head/tail ABI encoding of a value tuple."""
    if len(types) != len(values):
        raise ValueError("types/values length mismatch")
    return _encode_sequence([parse_type(t) for t in types], list(values))


# ---------------------------------------------------------------------------
# Decoding (strict: malformed offsets/lengths raise)
# ---------------------------------------------------------------------------


def _word_at(data: bytes, pos: int) -> bytes:
    if pos < 0 or pos + _WORD > len(data):
        raise ValueError("abi decode: word out of range")
    return data[pos : pos + _WORD]


def _decode_static_word(t: AbiType, word: bytes) -> Any:
    if t.base == "uint":
        return int.from_bytes(word, "big")
    if t.base == "bool":
        return bool(int.from_bytes(word, "big"))
    if t.base == "int":
        return int.from_bytes(word, "big", signed=True)
    if t.base == "address":
        return word[12:]
    if t.base == "fbytes":
        return word[: t.bits]
    raise ValueError(f"not a static word type: {t.base}")


def _decode_value(t: AbiType, data: bytes, pos: int) -> Any:
    if t.base in ("string", "bytes"):
        n = int.from_bytes(_word_at(data, pos), "big")
        raw = data[pos + _WORD : pos + _WORD + n]
        if len(raw) != n:
            raise ValueError("abi decode: truncated dynamic data")
        return raw.decode() if t.base == "string" else raw
    if t.base == "array":
        if t.length < 0:
            n = int.from_bytes(_word_at(data, pos), "big")
            # every element occupies ≥1 head word: a declared length beyond
            # that is malformed, not a multi-terabyte allocation
            need = n * t.elem.head_words
            if pos + _WORD + need * _WORD > len(data):
                raise ValueError("abi decode: array length exceeds calldata")
            return _decode_sequence([t.elem] * n, data, pos + _WORD)
        return _decode_sequence([t.elem] * t.length, data, pos)
    if t.base == "tuple":
        return _decode_sequence(list(t.components), data, pos)
    return _decode_static_word(t, _word_at(data, pos))


def _decode_sequence(types: list[AbiType], data: bytes, base: int) -> list[Any]:
    """Decode a head/tail sequence starting at `base`; dynamic offsets in
    the heads are relative to `base` (the enclosing frame)."""
    out: list[Any] = []
    pos = base
    for t in types:
        if t.is_dynamic:
            offset = int.from_bytes(_word_at(data, pos), "big")
            out.append(_decode_value(t, data, base + offset))
            pos += _WORD
        else:
            out.append(_decode_value(t, data, pos))
            pos += _WORD * t.head_words
    return out


def abi_decode(types: list[str], data: bytes) -> list[Any]:
    return _decode_sequence([parse_type(t) for t in types], data, 0)


# ---------------------------------------------------------------------------
# Compiled coders: a type list's shape worked out once, not once a call
# ---------------------------------------------------------------------------

_WORD_BASES = ("uint", "int", "bool", "address", "fbytes")


class Coder:
    """``encode(values) -> bytes`` and ``decode(data) -> list`` of one type
    list, built once (``coder_for``). A list of one-word types and
    ``string`` / ``bytes`` gets the direct form: every head is one word, so
    the heads are packed and unpacked in one pass with no type algebra. Any
    other list (arrays, tuples) keeps the generic coder above, its types
    parsed once. Both forms give the generic coder's bytes, values and
    errors: it is the reference the tests compare them against."""

    __slots__ = ("types", "encode", "decode")

    def __init__(self, types: tuple[str, ...]):
        self.types = types
        parsed = [parse_type(t) for t in types]
        if all(t.base in _WORD_BASES or t.base in ("string", "bytes") for t in parsed):
            self.encode, self.decode = _direct_coder(parsed)
        else:
            self.encode, self.decode = _generic_coder(parsed)


def _generic_coder(parsed: list[AbiType]):
    def encode(values) -> bytes:
        if len(values) != len(parsed):
            raise ValueError("types/values length mismatch")
        return _encode_sequence(parsed, list(values))

    def decode(data: bytes) -> list[Any]:
        return _decode_sequence(parsed, data, 0)

    return encode, decode


def _direct_coder(parsed: list[AbiType]):
    # per position (encode a word, decode a word, is text): the first two are
    # None for a string / bytes, whose head is the offset of its tail
    plan = [
        (partial(_encode_static_word, t), partial(_decode_static_word, t), False)
        if t.base in _WORD_BASES else (None, None, t.base == "string")
        for t in parsed
    ]
    n = len(plan)

    def encode(values) -> bytes:
        if len(values) != n:
            raise ValueError("types/values length mismatch")
        heads: list[bytes] = []
        tails: list[bytes] = []
        offset = _WORD * n
        for (enc, _dec, _text), v in zip(plan, values):
            if enc is not None:
                heads.append(enc(v))
                continue
            raw = v.encode() if isinstance(v, str) else bytes(v)
            tail = len(raw).to_bytes(_WORD, "big") + _pad_right(raw)
            heads.append(offset.to_bytes(_WORD, "big"))
            tails.append(tail)
            offset += len(tail)
        heads.extend(tails)
        return b"".join(heads)

    def decode(data: bytes) -> list[Any]:
        out: list[Any] = []
        size = len(data)
        pos = 0
        for _enc, dec, text in plan:
            end = pos + _WORD
            if end > size:
                raise ValueError("abi decode: word out of range")
            if dec is not None:
                out.append(dec(data[pos:end]))
            else:
                at = int.from_bytes(data[pos:end], "big")
                start = at + _WORD
                if start > size:
                    raise ValueError("abi decode: word out of range")
                length = int.from_bytes(data[at:start], "big")
                raw = data[start : start + length]
                if len(raw) != length:
                    raise ValueError("abi decode: truncated dynamic data")
                out.append(raw.decode() if text else raw)
            pos = end
        return out

    return encode, decode


@lru_cache(maxsize=4096)
def coder_for(types: tuple[str, ...]) -> Coder:
    return Coder(types)


@lru_cache(maxsize=4096)
def _signature_coder(signature: str) -> Coder:
    inner = signature[signature.index("(") + 1 : signature.rindex(")")]
    return coder_for(tuple(split_toplevel(inner)))


# ---------------------------------------------------------------------------
# Selector-aware codec
# ---------------------------------------------------------------------------


class ABICodec:
    """Selector-aware codec bound to a crypto suite's hash
    (reference: ContractABICodec + getFuncSelector). Every method goes
    through the compiled coder of its signature or type list."""

    def __init__(self, hash_fn):
        self._hash = hash_fn
        self.selector = lru_cache(maxsize=1024)(self._selector)

    def _selector(self, signature: str) -> bytes:
        return self._hash(signature.encode())[:4]

    def encode_call(self, signature: str, *values: Any) -> bytes:
        return self.selector(signature) + _signature_coder(signature).encode(values)

    def decode_input(self, signature: str, data: bytes) -> list[Any]:
        """Decode calldata that includes the 4-byte selector."""
        return _signature_coder(signature).decode(data[4:])

    def encode_output(self, types: list[str], *values: Any) -> bytes:
        return coder_for(tuple(types)).encode(values)

    def decode_output(self, types: list[str], data: bytes) -> list[Any]:
        return coder_for(tuple(types)).decode(data)

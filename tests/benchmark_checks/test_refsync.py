"""``benchmark/refsync.py`` held to ``refcrypto.py``'s and ``refsm.py``'s own
vectors and to bytes laid out by hand: the key it recovers is the signer's,
a signature outside the range check recovers none, the wire layout it reads
is the one the program writes, a header is quorum-signed exactly when two
thirds of the committee and one more signed it, and a backlog is judged block
by block up to the first that fails."""

import struct

import pytest

from benchmark import refcrypto, refsm, refsync

SECRETS = [0xA11CE, 0xB0B, refcrypto.N - 2, 0x5EED_0000_0000_0001]
N = refcrypto.N


def blob(b: bytes) -> bytes:
    return struct.pack("<I", len(b)) + b


def seq(items) -> bytes:
    return struct.pack("<I", len(items)) + b"".join(items)


def user_add_call(suite, user: str, amount: int) -> bytes:
    name = user.encode()
    return (suite.hash(refsync.USER_ADD)[:4] + (64).to_bytes(32, "big")
            + amount.to_bytes(32, "big") + len(name).to_bytes(32, "big")
            + name + bytes(-len(name) % 32))


def tx_data(suite, nonce: str, user: str, amount: int, to=refsync.DAG_TRANSFER, version=0) -> bytes:
    return (struct.pack("<I", version) + blob(b"chain0") + blob(b"group0") + struct.pack("<q", 500)
            + blob(nonce.encode()) + blob(to) + blob(user_add_call(suite, user, amount)) + blob(b""))


def wire(data: bytes, sig: bytes) -> bytes:
    return blob(data) + blob(sig) + struct.pack("<I", 0) + struct.pack("<q", 0) + blob(b"")


def sign(suite, data: bytes, secret: int) -> bytes:
    if suite is refsync.Sm:
        return refsm.sign_tx(data, secret)
    return refcrypto.sign(refcrypto.keccak256(data), secret)


def pub_of(suite, secret: int) -> bytes:
    return (refsm if suite is refsync.Sm else refcrypto).pubkey_bytes(secret)


def header(suite, number: int, parent: bytes, sealers: list[bytes], signers: dict[int, int],
           state_root=bytes(32)) -> bytes:
    """An encoded header signed by ``signers`` (committee index -> secret)."""
    pre = (struct.pack("<I", 0) + seq([struct.pack("<q", number - 1) + parent])
           + bytes(32) + bytes(32) + state_root + struct.pack("<q", number)
           + struct.pack("<Q", 0) + struct.pack("<q", 0) + struct.pack("<q", 0)
           + seq([blob(s) for s in sealers]) + blob(b"") + seq([struct.pack("<Q", 1)] * len(sealers)))
    digest = suite.hash(pre)
    if suite is refsync.Sm:
        def sig(secret):
            r, s = refsm.sign(digest, secret)
            return r.to_bytes(32, "big") + s.to_bytes(32, "big") + refsm.pubkey_bytes(secret)
    else:
        def sig(secret):
            return refcrypto.sign(digest, secret)
    sigs = [struct.pack("<q", i) + blob(sig(secret)) for i, secret in signers.items()]
    return blob(pre) + seq(sigs)


def block(head: bytes, txs: list[bytes]) -> bytes:
    return blob(head) + seq([blob(t) for t in txs]) + seq([]) + seq([])


SUITES = [pytest.param(refsync.Secp, id="secp"), pytest.param(refsync.Sm, id="sm")]


@pytest.mark.parametrize("secret", SECRETS)
def test_recovery_names_the_signer_of_refcryptos_own_signatures(secret):
    digest = refcrypto.keccak256(b"block sync " + secret.to_bytes(32, "big"))
    sig = refcrypto.sign(digest, secret)
    assert refsync._secp_recover(digest, sig) == refcrypto.pubkey_bytes(secret)
    assert refcrypto.verify(digest, sig, refsync._secp_recover(digest, sig))
    # the other parity names another key, and that key verifies too
    other = refsync._secp_recover(digest, sig[:64] + bytes([sig[64] ^ 1]))
    assert other != refcrypto.pubkey_bytes(secret) and refcrypto.verify(digest, sig, other)


@pytest.mark.parametrize("how", ["r=0", "s=0", "r=n", "s=n", "v=4", "short"])
def test_a_signature_outside_the_range_check_recovers_nothing(how):
    data = tx_data(refsync.Secp, "n0", "alice", 5)
    sig = refcrypto.sign(refcrypto.keccak256(data), SECRETS[0])
    zero, order = bytes(32), N.to_bytes(32, "big")
    bad = {"r=0": zero + sig[32:], "s=0": sig[:32] + zero + sig[64:],
           "r=n": order + sig[32:], "s=n": sig[:32] + order + sig[64:],
           "v=4": sig[:64] + b"\x04", "short": sig[:64]}[how]
    ok, digest, sender = refsync.Secp.admit(data, bad)
    assert (ok, sender) == (False, b"") and digest == refcrypto.keccak256(data)
    assert refsync.Secp.admit(data, sig) == (
        True, digest, refcrypto.address(refcrypto.pubkey_bytes(SECRETS[0])))


@pytest.mark.parametrize("how", ["r=0", "s=0", "r=n", "s=n", "neighbours_key", "short"])
def test_the_sm_suite_admits_what_refsm_admits(how):
    data = tx_data(refsync.Sm, "n0", "alice", 5)
    sig = refsm.sign_tx(data, SECRETS[0])
    zero, order = bytes(32), refsm.N.to_bytes(32, "big")
    bad = {"r=0": zero + sig[32:], "s=0": sig[:32] + zero + sig[64:],
           "r=n": order + sig[32:], "s=n": sig[:32] + order + sig[64:],
           "neighbours_key": sig[:64] + refsm.pubkey_bytes(SECRETS[1]), "short": sig[:64]}[how]
    assert refsync.Sm.admit(data, bad) == (False, refsm.sm3(data), b"")
    assert refsync.Sm.admit(data, sig) == (
        True, refsm.sm3(data), refsm.address(refsm.pubkey_bytes(SECRETS[0])))


@pytest.mark.parametrize("suite", SUITES)
def test_the_layout_read_is_the_layout_the_program_writes(suite):
    """A block built by the program's own encoders comes apart into the same
    header hash, transactions, call and signature the program holds."""
    from fisco_bcos_tpu.codec.abi import ABICodec
    from fisco_bcos_tpu.crypto.suite import ecdsa_suite, sm_suite
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from fisco_bcos_tpu.protocol.block import Block
    from fisco_bcos_tpu.protocol.block_header import BlockHeader, ParentInfo, SignatureTuple
    from fisco_bcos_tpu.protocol.transaction import TransactionFactory

    s = sm_suite() if suite is refsync.Sm else ecdsa_suite()
    kp = s.signature_impl.generate_keypair(secret=SECRETS[1])
    txs = [
        TransactionFactory(s).create_signed(
            kp, chain_id="chain0", group_id="group0", block_limit=500, nonce=f"n{i}",
            to=DAG_TRANSFER_ADDRESS,
            input=ABICodec(s.hash).encode_call("userAdd(string,uint256)", f"user-{i}", 10 + i))
        for i in range(3)
    ]
    head = BlockHeader(
        number=7, parent_info=[ParentInfo(6, b"\x06" * 32)], state_root=b"\x07" * 32,
        sealer_list=[b"\x01" * 64, b"\x02" * 64], consensus_weights=[1, 1],
        signature_list=[SignatureTuple(1, b"\x09" * s.signature_impl.sig_len)])
    raw = Block(header=head, transactions=txs).encode()
    head_bytes, wires = refsync.split_block(raw)
    h = refsync.split_header(head_bytes)
    assert (h["number"], h["parent"], h["state_root"]) == (7, b"\x06" * 32, b"\x07" * 32)
    assert h["sealers"] == head.sealer_list and suite.hash(h["preimage"]) == head.hash(s)
    assert h["signatures"] == [(1, b"\x09" * s.signature_impl.sig_len)]
    assert wires == [t.encode() for t in txs]
    for i, (w, t) in enumerate(zip(wires, txs)):
        data, sig = refsync.split_tx(w)
        assert (data, sig) == (t.encode_data(), bytes(t.signature))
        assert refsync.call_of(data) == (DAG_TRANSFER_ADDRESS, t.input)
        assert refsync.user_add_of(data, suite) == (f"user-{i}", 10 + i)
        assert suite.admit(data, sig) == (True, t.hash(s), s.calculate_address(kp.pub))
    # and the layout written by hand above is the program's
    assert tx_data(suite, "n1", "user-1", 11, version=txs[1].version) == txs[1].encode_data()


@pytest.mark.parametrize("suite", SUITES)
def test_a_header_is_quorum_signed_by_two_thirds_and_one_more(suite):
    secrets = [11, 22, 33, 44]
    order = sorted(range(4), key=lambda i: pub_of(suite, secrets[i]))
    sealers = [pub_of(suite, secrets[i]) for i in order]
    committee = [pub_of(suite, x) for x in secrets]  # in any order
    signers = lambda *idx: {i: secrets[order[i]] for i in idx}  # noqa: E731
    assert refsync.quorum_signed(header(suite, 1, bytes(32), sealers, signers(0, 1, 3)), committee, suite)
    assert refsync.quorum_signed(header(suite, 1, bytes(32), sealers, signers(0, 1, 2, 3)), committee, suite)
    assert not refsync.quorum_signed(header(suite, 1, bytes(32), sealers, signers(0, 2)), committee, suite)
    # a member signing under another's index, an index out of range, another committee
    assert not refsync.quorum_signed(
        header(suite, 1, bytes(32), sealers, {0: secrets[order[0]], 1: secrets[order[2]], 2: secrets[order[2]]}),
        committee, suite)
    assert not refsync.quorum_signed(
        header(suite, 1, bytes(32), sealers, {0: secrets[order[0]], 1: secrets[order[1]], 4: secrets[order[2]]}),
        committee, suite)
    assert not refsync.quorum_signed(
        header(suite, 1, bytes(32), sealers[:3], signers(0, 1, 2)), committee, suite)


@pytest.mark.parametrize("suite", SUITES)
def test_a_backlog_is_judged_up_to_the_first_block_that_fails(suite):
    secrets = [11, 22, 33, 44]
    sealers = sorted(pub_of(suite, x) for x in secrets)
    by_pub = {pub_of(suite, x): x for x in secrets}
    quorum = {i: by_pub[sealers[i]] for i in (0, 1, 2)}

    def chain(breaks=None, unsigned=None, unlinked=None):
        raws, parent = [], bytes(32)
        for n in (1, 2, 3):
            txs = []
            for i in range(3):
                data = tx_data(suite, f"n{n}-{i}", f"user-{(n * 3 + i) % 7}", 100 * n + i)
                sig = sign(suite, data, SECRETS[i % 2])
                if breaks == (n, i):
                    sig = bytes(32) + sig[32:]
                txs.append(wire(data, sig))
            head = header(suite, n, bytes(32) if unlinked == n else parent, sealers,
                          {0: quorum[0]} if unsigned == n else quorum,
                          state_root=bytes([n]) * 32)
            parent = suite.hash(refsync.split_header(head)["preimage"])
            raws.append(block(head, txs))
        return raws

    sound = refsync.judge(chain(), [pub_of(suite, x) for x in secrets], suite)
    assert [b["applied"] for b in sound["blocks"]] == [True] * 3 and sound["height"] == 3
    assert [b["state_root"] for b in sound["blocks"]] == [bytes([n]) * 32 for n in (1, 2, 3)]
    senders = {i: (refsm.address if suite is refsync.Sm else refcrypto.address)(
        pub_of(suite, SECRETS[i])) for i in (0, 1)}
    assert sound["blocks"][0]["senders"] == [senders[0], senders[1], senders[0]]
    # userAdd: the first write of a user wins (users repeat mod 7)
    assert sound["balances"] == {
        "user-3": 100, "user-4": 101, "user-5": 102, "user-6": 200, "user-0": 201,
        "user-1": 202, "user-2": 300}
    for kwargs, want, flag in (
        ({"breaks": (2, 1)}, [True, False, False], "admits"),
        ({"unsigned": 2}, [True, False, False], "qc"),
        ({"unlinked": 3}, [True, True, False], None),
    ):
        got = refsync.judge(chain(**kwargs), [pub_of(suite, x) for x in secrets], suite)
        assert [b["applied"] for b in got["blocks"]] == want
        assert got["height"] == want.index(False)
        if flag:
            assert [b[flag] for b in got["blocks"]] == [True, False, True]
        assert set(got["balances"]) <= set(sound["balances"])
        assert ("user-2" in got["balances"]) is False

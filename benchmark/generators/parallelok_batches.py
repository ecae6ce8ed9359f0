"""Traffic generator for the deployed-contract configuration: what
``dag_transfer_batches`` draws (the same keys, names, opening balances, Zipf
pairs and amounts from the same seed), signed as calls to a deployed
``ParallelOk`` and not to the DagTransfer precompile:

- ``deploy``   one ``create`` transaction: the contract's creation code
               (a 12-byte constructor that returns the runtime of
               ``benchmark/contracts/ParallelOk.runtime.hex``) carrying the
               ABI of ``ParallelOk.abi.json`` with its ``conflictFields``;
- ``opening``  ``set(name, balance)`` batches, ``attribute`` 0;
- ``batches``  ``transfer(from, to, num)``, every one carrying
               ``TransactionAttribute.DAG``.

A call can be signed only to an address, and the address of a created
contract is the chain's to give. Its rule is upstream's
(``ChecksumAddress.h`` ``newEVMAddress``): the first 20 bytes of the hash of
``"<block number>_<context id>_<seq>"``. The driver commits ``deploy`` alone in
the next block of a chain at rest (context 0, seq 0), so the corpus works the
address out as an SDK would, ``contract``, and signs to it; the driver holds
the receipt's address to it."""

from __future__ import annotations

import os

from benchmark.generators import dag_transfer_batches

CONTRACTS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "contracts")
SET = "set(string,uint256)"
TRANSFER = "transfer(string,string,uint256)"


def creation_code(runtime: bytes) -> bytes:
    """PUSH2 len, DUP1, PUSH1 12, PUSH1 0, CODECOPY, PUSH1 0, RETURN ++ runtime:
    the constructor copies the runtime behind it to memory and returns it."""
    return (b"\x61" + len(runtime).to_bytes(2, "big")
            + bytes.fromhex("80600c6000396000f3") + runtime)


def contract_files(name: str) -> tuple[bytes, str]:
    """-> (the runtime's bytes, the ABI's JSON text) of ``contracts/<name>.*``."""
    with open(os.path.join(CONTRACTS, name + ".runtime.hex")) as f:
        runtime = bytes.fromhex(f.read().strip())
    with open(os.path.join(CONTRACTS, name + ".abi.json")) as f:
        return runtime, f.read()


class Corpus(dag_transfer_batches.Corpus):
    """``dag_transfer_batches.Corpus`` with the callee a deployed contract:
    the same ``records`` and ``opening_records`` for the same seed."""

    def __init__(self, config: dict, traffic: dict, seed: int, block_limit: int):
        from fisco_bcos_tpu.crypto.suite import ecdsa_suite

        super().__init__(config, traffic, seed, block_limit)
        runtime, abi = contract_files(config["contract"])
        created = self._factory.create_signed(
            self._keys[0], chain_id="chain0", group_id="group0",
            block_limit=block_limit, nonce=f"c{seed:x}", to=b"",
            input=creation_code(runtime), abi=abi,
        )
        self.deploy = self._factory.decode(created.encode())  # as it arrives on the wire
        # block_limit is the chain's head + block_limit_ahead: deploy's block is the next
        number = block_limit - int(config["block_limit_ahead"]) + 1
        self.contract = self._to = ecdsa_suite().hash(f"{number}_0_0".encode())[:20]

    def _signed(self, who: int, nonce: str, attribute: int, sig: str, *args):
        # the draws are the precompile corpus's; the two calls are the contract's
        sig = {dag_transfer_batches.USER_ADD: SET, dag_transfer_batches.USER_TRANSFER: TRANSFER}[sig]
        return super()._signed(who, nonce, attribute, sig, *args)

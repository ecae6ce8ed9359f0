"""big-committee: a 4-node PBFT chain whose committee registered BLS
``qc_pub`` keys commits blocks end to end; the committed headers must carry
the constant-size QC record (and no signature_list) and the replicas must
agree. ``tool/check_qc.py`` drives it; the committee is derived from the
seed, so a run is reproducible.
"""

from __future__ import annotations

import time


def _chain_leg(seed: int, blocks: int = 2) -> dict:
    """End-to-end: a 4-node chain whose committee registered BLS qc_pubs
    commits real blocks; committed headers must carry the constant-size
    QC record."""
    from ..codec.abi import ABICodec
    from ..consensus.qc import QuorumCert, qc_pub_for
    from ..crypto.suite import ecdsa_suite
    from ..executor.precompiled import DAG_TRANSFER_ADDRESS
    from ..front import InprocGateway
    from ..ledger import ConsensusNode, GenesisConfig
    from ..node import Node, NodeConfig
    from ..protocol.transaction import TransactionFactory

    suite = ecdsa_suite()
    codec = ABICodec(suite.hash)
    secrets = [0xB1C0_0000 + seed * 1000 + i for i in range(4)]
    keypairs = [suite.signature_impl.generate_keypair(secret=s) for s in secrets]
    committee = [
        ConsensusNode(kp.pub, weight=1, qc_pub=qc_pub_for(s))
        for kp, s in zip(keypairs, secrets)
    ]
    gw = InprocGateway(auto=True)
    nodes = []
    for kp in keypairs:
        cfg = NodeConfig(genesis=GenesisConfig(consensus_nodes=list(committee)))
        node = Node(cfg, keypair=kp)
        gw.connect(node.front)
        nodes.append(node)
    fac = TransactionFactory(suite)
    sender = suite.signature_impl.generate_keypair(secret=0xB1C0_FFFF)
    committed_qc_bytes = []
    t0 = time.perf_counter()
    for b in range(blocks):
        height = nodes[0].block_number() + 1
        idx = nodes[0].pbft_config.leader_index(height, 0)
        leader = next(
            nd
            for nd in nodes
            if nd.node_id == nodes[0].pbft_config.nodes[idx].node_id
        )
        txs = [
            fac.create_signed(
                sender,
                chain_id="chain0",
                group_id="group0",
                block_limit=500,
                nonce=f"bigc-{seed}-{b}-{i}",
                to=DAG_TRANSFER_ADDRESS,
                input=codec.encode_call(
                    "userAdd(string,uint256)", f"u{b}-{i}", 1
                ),
            )
            for i in range(3)
        ]
        leader.txpool.submit_batch(txs)
        leader.tx_sync.maintain()
        leader.sealer.seal_and_submit()
        header = leader.ledger.header_by_number(leader.block_number())
        if header is not None and header.qc:
            cert = QuorumCert.decode(header.qc)
            committed_qc_bytes.append(len(header.qc))
            assert cert.scheme == "bls", cert.scheme
    heights = {nd.block_number() for nd in nodes}
    return {
        "blocks_committed": nodes[0].block_number(),
        "wall_s": round(time.perf_counter() - t0, 3),
        "heights_equal": len(heights) == 1,
        "committed_qc_bytes": committed_qc_bytes,
        "headers_carry_qc": len(committed_qc_bytes) == nodes[0].block_number(),
    }

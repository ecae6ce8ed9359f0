"""A receipt's wire form through the flat codec, field by field: the layout
``TransactionReceipt.encode`` packs directly, written out as the reference of
``tests/test_protocol.py`` and ``tests/test_executor.py``."""

from fisco_bcos_tpu.codec.flat import FlatWriter


def flat_receipt(rc) -> bytes:
    w = FlatWriter()
    w.u32(rc.version).u64(rc.gas_used).bytes_(rc.contract_address).u32(rc.status)
    w.bytes_(rc.output)
    w.seq(rc.log_entries, lambda w2, e: e.encode_into(w2))
    w.i64(rc.block_number).str_(rc.effective_gas_price)
    return w.out()

"""Programs of the main path compiled for the chip without the chip: the
TPU's compiler is installed here and compiles for a v5e that is described and
not attached. Nothing runs, so this says nothing about results or times: it
holds a program to what the chip's compiler accepts, at its real size.

The topology is described inside a fixture (one process at a time may load
the TPU's library, and a worker loads it only if it is given this file), and
every such test lives in this one file."""

import os

import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache and cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("leaves", [1024, 10_240])
def test_the_sm3_merkle_tree_compiles_for_the_chip(one_chip, leaves):
    """A block's SM3 tree (1,000 transactions pad to 1,024 leaves; the
    headline 10,000 to 10,240) is one program the v5e's compiler accepts:
    every level above the leaves comes back as the rows of one array."""
    import jax
    import jax.numpy as jnp

    from fisco_bcos_tpu.ops import merkle

    x = jax.ShapeDtypeStruct((leaves, 32), jnp.uint8, sharding=one_chip)
    compiled = merkle._device_tree_fn("sm3", leaves, 16).lower(x).compile()
    rows = merkle._level_offsets(leaves, 16)[-1] + 1
    (out,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (rows, 32) and out.dtype == jnp.uint8

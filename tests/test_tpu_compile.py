"""The save-path kernels compile for a TPU v5e at mamba2-370m widths.

The TPU compiler is installed without a chip, and it refuses what the
interpreter accepts (bitwidth-changing bitcasts, unsigned reductions,
scalar stores to VMEM).  Each test compiles one kernel wrapper for a
described ``v5e:2x2`` topology and checks that the Pallas kernel is in
the program (``tpu_custom_call``).  Nothing runs.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_fp import ops as fp_ops
from repro.kernels.block_fp.ref import DEFAULT_BLOCK_BYTES
from repro.kernels.block_gather import ops as gather_ops

# mamba2-370m leaves: one layer's (2048, 1024) output projection as
# weights (bf16) and optimizer state (fp32), and the 50280 x 1024
# embedding, whose last 64 KiB block is padded
LEAVES = [((2048, 1024), jnp.bfloat16), ((2048, 1024), jnp.float32),
          ((50280, 1024), jnp.bfloat16)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _n_blocks(shape, dtype) -> int:
    nbytes = jnp.dtype(dtype).itemsize
    for d in shape:
        nbytes *= d
    return -(-nbytes // DEFAULT_BLOCK_BYTES)


@pytest.mark.parametrize("shape,dtype", LEAVES)
def test_block_fp_compiles_for_v5e(one_chip, shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn = functools.partial(fp_ops._fingerprint,
                           block_bytes=DEFAULT_BLOCK_BYTES,
                           n_blocks=_n_blocks(shape, dtype), impl="pallas")
    hlo = jax.jit(fn).lower(x).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("capacity", [1, 64, 128])
@pytest.mark.parametrize("shape,dtype", LEAVES)
def test_block_gather_compiles_for_v5e(one_chip, shape, dtype, capacity):
    nb = _n_blocks(shape, dtype)
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    ref = jax.ShapeDtypeStruct((nb, 2), jnp.uint32, sharding=one_chip)

    def fn(a, r):
        return gather_ops._gather_many(
            (a,), (r,), block_bytes=DEFAULT_BLOCK_BYTES, n_blocks=(nb,),
            capacities=(capacity,), impl="pallas", quant=False)

    hlo = jax.jit(fn).lower(x, ref).compile().as_text()
    # the fingerprint kernel and the copy of the listed blocks
    assert hlo.count("tpu_custom_call") >= 2

"""Dirty-block gather kernel (Pallas): copy the listed blocks of a leaf
into a dense ``(capacity, ...)`` buffer, so the device->host copy ships
exactly the changed bytes plus a tiny index vector instead of full arrays.

The fused gather of ``ops.py`` runs the ``block_fp`` fingerprint kernel
over every block, compacts the dirty indices with a few tiny XLA ops on
the ``(n_blocks,)`` flag vector, and then runs this kernel over only the
``capacity`` listed blocks — all inside one jitted dispatch.  The index
vector is scalar-prefetched into SMEM and drives the input ``BlockSpec``'s
``index_map``, so the Pallas pipeline DMAs one listed block per grid step
through VMEM: no state is carried across grid steps and no output buffer
stays resident, so the capacity is bounded by HBM, not by VMEM.

Blocks are the ``block_fp`` slabs — ``(n_blocks, sub, lanes)`` integer
views — so a block is one leading index and its tile is the array's own
last two dimensions, which the TPU's tiling rule always accepts.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy_kernel(idx_ref, slab_ref, out_ref):
    del idx_ref  # consumed by the index_map
    out_ref[...] = slab_ref[...]


def gather_slabs(slabs: jax.Array, idx: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """slabs: (n_blocks, sub, lanes), idx: (capacity,) int32 in
    [0, n_blocks) -> (capacity, sub, lanes) with row c = slabs[idx[c]]."""
    _, sub, lanes = slabs.shape
    block = (None, sub, lanes)
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(idx.shape[0],),
            in_specs=[pl.BlockSpec(block, lambda c, ix: (ix[c], 0, 0))],
            out_specs=pl.BlockSpec(block, lambda c, ix: (c, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((idx.shape[0], sub, lanes),
                                       slabs.dtype),
        interpret=interpret,
        name="block_gather",
    )(idx, slabs)

"""Block fingerprint kernel (Pallas): one fused pass over a checkpoint
unit's data computes, per 64 KiB block, a Fletcher-style 32-bit checksum
pair plus an advisory float32 sum-of-squares.

This is the device half of the save-path fast detector: the fingerprint
vector is ~0.02% the size of the data, so comparing it against the previous
save's vector on device tells the saver which blocks actually need the
device->host transfer, the hash, and the delta encode — the costs that used
to scale with model size now scale with drift.

Grid: tiles of ``rows`` blocks; each block is reduced entirely in VMEM
(pure VPU work — integer multiply-accumulate and a float square-sum; no
MXU).  What the TPU's compiler accepts shapes the layout:

- the kernel reads a same-width *signed integer* view of the data
  (int8/int16/int32; the bitcast happens in the jitted wrapper and is
  free there), because Mosaic neither changes bitwidths inside a kernel
  nor loads every float dtype;
- each block is a ``(SUB, elems // SUB)`` slab of a 3-D ``(n_blocks, SUB,
  elems // SUB)`` array, so the block's last two dimensions are the
  array's own and satisfy the tiling rule at any block size, and the
  gather kernel can address one block by its leading index;
- the little-endian 32-bit word a narrow element belongs to is never
  materialized: an element contributes its zero-extended bits shifted to
  its byte position within the word, weighted by its word index — the
  same sums as the uint32 oracle in ``ref.py``;
- the sums run in wrap-around int32 (Mosaic has no unsigned reductions),
  which is the oracle's uint32 arithmetic bit for bit, reinterpreted as
  uint32 outside the kernel.

The float sumsq is advisory only (drift scoring) and never hashed or
compared for equality: float32 and bfloat16 decode from their bits in the
kernel, other dtypes are reduced from the original values by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: sublane rows each block is split into (1 when the block's element
#: count is not a multiple of it)
SUB = 8

_INT_OF_SIZE = {1: jnp.int8, 2: jnp.int16, 4: jnp.int32}


def block_slabs(flat: jax.Array, epb: int) -> jax.Array:
    """(n_blocks * epb,) -> the (n_blocks, sub, epb // sub) slab view
    both kernels read."""
    sub = SUB if epb % SUB == 0 else 1
    return flat.reshape(-1, sub, epb // sub)


def int_view(x: jax.Array) -> jax.Array:
    """Same-width signed integer view of ``x`` (8-byte dtypes become
    int32 word pairs along a new minor axis, folded into the last one)."""
    itemsize = jnp.dtype(x.dtype).itemsize
    if itemsize == 8:
        w = jax.lax.bitcast_convert_type(x, jnp.int32)
        return w.reshape(x.shape[:-1] + (x.shape[-1] * 2,))
    return jax.lax.bitcast_convert_type(x, _INT_OF_SIZE[itemsize])


def from_int_view(v: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`int_view` for (rows, n) arrays, bit for bit."""
    if jnp.dtype(dtype).itemsize == 8:
        rows, n = v.shape
        return jax.lax.bitcast_convert_type(v.reshape(rows, n // 2, 2),
                                            dtype)
    return jax.lax.bitcast_convert_type(v, dtype)


def _placed_words(x: jax.Array):
    """(rows, sub, lanes) int tile -> (each element's bits at its byte
    position in its little-endian word, each element's 1-based word
    index), both int32."""
    _, sub, lanes = x.shape
    itemsize = jnp.dtype(x.dtype).itemsize
    per = 4 // itemsize
    pos = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) * lanes
           + jax.lax.broadcasted_iota(jnp.int32, x.shape, 2))
    if per == 1:
        return x, pos + 1
    bits = 8 * itemsize
    v = x.astype(jnp.int32) & ((1 << bits) - 1)
    return v << ((pos % per) * bits), pos // per + 1


def _sum_rows(v: jax.Array, dtype) -> jax.Array:
    """(rows, sub, lanes) -> (rows, 1), accumulating in ``dtype`` (pinned
    so int32 sums wrap mod 2^32 even under jax_enable_x64)."""
    return jnp.sum(jnp.sum(v, axis=2, dtype=dtype), axis=1, keepdims=True,
                   dtype=dtype)


def _decoded(x: jax.Array, decode: str) -> jax.Array:
    """float32 values behind an int tile: ``"float32"`` is a bitcast, a
    bfloat16's float32 value is its 16 bits in the high half of a word."""
    if decode == "float32":
        return jax.lax.bitcast_convert_type(x, jnp.float32)
    return jax.lax.bitcast_convert_type(
        (x.astype(jnp.int32) & 0xFFFF) << 16, jnp.float32)


def checksums(x: jax.Array):
    """(rows, sub, lanes) int tile -> the per-block (fp1, fp2) pair, each
    (rows, 1) int32 — the kernel body's math, also run as plain jnp by
    the non-TPU path."""
    words, weights = _placed_words(x)
    return (_sum_rows(words, jnp.int32),
            _sum_rows(words * weights, jnp.int32))


def _fp_kernel(x_ref, fp_ref, *ss_ref, decode):
    x = x_ref[...]                                  # (rows, sub, lanes)
    fp_ref[:, 0:1], fp_ref[:, 1:2] = checksums(x)
    if ss_ref:
        v = _decoded(x, decode)
        ss_ref[0][...] = _sum_rows(v * v, jnp.float32)


def fingerprint_slabs(slabs: jax.Array, dtype, *, rows_per_tile: int = 8,
                      interpret: bool = False):
    """slabs: (n_blocks, sub, lanes) int view of blocks of ``dtype`` ->
    (fp (n_blocks, 2) uint32, sumsq (n_blocks,) float32, or None when
    ``dtype`` does not decode in the kernel)."""
    nb, sub, lanes = slabs.shape
    rows = min(rows_per_tile, nb)
    assert nb % rows == 0, (nb, rows)
    name = jnp.dtype(dtype).name
    decode = name if name in ("float32", "bfloat16") else None
    out_specs = [pl.BlockSpec((rows, 2), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((nb, 2), jnp.int32)]
    if decode:
        out_specs.append(pl.BlockSpec((rows, 1), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((nb, 1), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_fp_kernel, decode=decode),
        grid=(nb // rows,),
        in_specs=[pl.BlockSpec((rows, sub, lanes), lambda i: (i, 0, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="block_fp",
    )(slabs)
    fp = jax.lax.bitcast_convert_type(outs[0], jnp.uint32)
    return fp, (outs[1][:, 0] if decode else None)

"""jit'd wrappers for the fused gather: per-leaf and per-unit dispatch,
capacity rounding, and the optional on-device int8 composition.

``interpret=None`` auto-selects exactly like ``block_fp.ops``: the Pallas
kernels on TPU (``block_fp`` fingerprints, then the ``block_gather`` copy
of the listed blocks), an op-identical plain-jnp path elsewhere (same
word view, same wrap-around sums, ``jnp.take`` for the copy); both
compact with ``jnp.nonzero(size=capacity)``, so results are
bit-identical.  Pass
``interpret=True`` to force the Pallas kernel through the interpreter
(how the property tests exercise the kernel body off-TPU).

Capacity is a STATIC shape: the caller predicts it (advisory — e.g. from
DeltaTracker drift signals), :func:`round_capacity` rounds it up to a
power of two so recompilation is bounded at O(log n_blocks) variants per
leaf structure, and the returned ``count`` is authoritative — ``count >
capacity`` means the prediction was short and the caller re-gathers with
a larger buffer.  The dense buffer lives in HBM on every path, so any
capacity up to ``n_blocks`` runs on the Pallas kernels.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.block_fp.kernel import from_int_view
from repro.kernels.block_fp.ops import (
    _as_blocks,
    _as_slabs,
    _block_elems,
    _device_groups,
    _fingerprint_jnp,
    _fingerprint_pallas,
    _impl,
)
from repro.kernels.block_fp.ref import DEFAULT_BLOCK_BYTES
from repro.kernels.block_gather.kernel import gather_slabs

QUANT_BLOCK = 256  # quantize codec's elements per scale


@dataclasses.dataclass
class GatherResult:
    """Device results of one leaf's fused gather (fetch only what you
    need: ``fp``/``idx``/``count`` are tiny, ``blocks`` is the payload)."""
    fp: Any          # (n_blocks, 2) uint32
    sumsq: Any       # (n_blocks,) float32 — advisory
    idx: Any         # (capacity,) int32, dirty indices ascending, -1 fill
    blocks: Any      # (capacity, elems_per_block) leaf dtype, zero fill
    count: Any       # () int32 — TOTAL dirty blocks (may exceed capacity)
    q: Any = None    # (nq, QUANT_BLOCK) int8 when quantized
    scales: Any = None  # (nq, 1) float32 when quantized

    @property
    def capacity(self) -> int:
        return int(self.idx.shape[0])


def round_capacity(n: int, n_blocks: int) -> int:
    """Round a predicted dirty-block count up to a power of two, clamped
    to [1, n_blocks] — the static-shape discipline that bounds jit
    recompilation."""
    n = max(1, min(int(n), int(n_blocks)))
    cap = 1
    while cap < n:
        cap *= 2
    return min(cap, int(n_blocks))


def _quantize_jnp(x: jax.Array, block: int):
    """The quantize kernel's math as plain jnp (bit-identical: amax/127
    scale, round-half-even, clip)."""
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % block
    flat = jnp.pad(flat, (0, pad))
    b = flat.reshape(-1, block)
    amax = jnp.max(jnp.abs(b), axis=1, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(b / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _gather_one(x, ref, *, block_bytes, n_blocks, capacity, impl, quant):
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    ref = jnp.asarray(ref, jnp.uint32)
    if impl == "jnp":
        fp, ss = _fingerprint_jnp(x, block_bytes)
    else:
        slabs = _as_slabs(x, block_bytes, pad_rows=True)
        fp, ss = _fingerprint_pallas(x, slabs, block_bytes, impl)
        fp, ss = fp[:n_blocks], ss[:n_blocks]
    dirty = jnp.any(fp != ref, axis=1)
    count = jnp.sum(dirty, dtype=jnp.int32)
    (idx,) = jnp.nonzero(dirty, size=capacity, fill_value=-1)
    idx = idx.astype(jnp.int32)
    valid = idx >= 0
    safe = jnp.where(valid, idx, 0)
    if impl == "jnp":
        blocks = _as_blocks(x, _block_elems(x.dtype, block_bytes),
                            pad_rows=False)
        taken = jnp.take(blocks, safe, axis=0)
    else:
        taken = gather_slabs(slabs, safe,
                             interpret=impl == "pallas-interpret")
        taken = from_int_view(taken.reshape(capacity, -1), x.dtype)
    out = jnp.where(valid[:, None], taken, jnp.zeros((), x.dtype))
    if not quant:
        return fp, ss, idx, out, count, None, None
    q, scales = _quantize_jnp(out, QUANT_BLOCK)
    return fp, ss, idx, out, count, q, scales


@functools.partial(jax.jit, static_argnames=("block_bytes", "n_blocks",
                                             "capacities", "impl", "quant"))
def _gather_many(xs, refs, *, block_bytes, n_blocks, capacities, impl,
                 quant):
    """All of a unit's leaves in ONE dispatch (same rationale as
    ``block_fp._fingerprint_many``: per-leaf dispatch overhead would dwarf
    the work on small hosts — and the overlap saver must dispatch a whole
    unit's device work before donated buffers are reused)."""
    return tuple(
        _gather_one(x, r, block_bytes=block_bytes, n_blocks=nb,
                    capacity=c, impl=impl, quant=quant)
        for x, r, nb, c in zip(xs, refs, n_blocks, capacities))


def gather_dirty(x: jax.Array, ref_fp, *, capacity: int,
                 block_bytes: int = DEFAULT_BLOCK_BYTES,
                 interpret: Optional[bool] = None,
                 quantize_int8: bool = False) -> GatherResult:
    """Fused fingerprint + compare-vs-``ref_fp`` + dirty-block compaction
    of one array.  ``capacity`` is rounded up via :func:`round_capacity`."""
    x = jnp.asarray(x)
    epb = _block_elems(
        jnp.uint8 if x.dtype == jnp.bool_ else x.dtype, block_bytes)
    nb = max(1, -(-x.size // epb))
    (res,) = _gather_many(
        (x,), (jnp.asarray(ref_fp, jnp.uint32),), block_bytes=block_bytes,
        n_blocks=(nb,), capacities=(round_capacity(capacity, nb),),
        impl=_impl(interpret), quant=quantize_int8)
    return GatherResult(*res)


def gather_tree_dirty(arrs: Sequence[jax.Array], ref_fps: Sequence[Any],
                      capacities: Sequence[int], *,
                      block_bytes: int = DEFAULT_BLOCK_BYTES,
                      interpret: Optional[bool] = None,
                      quantize_int8: bool = False) -> List[GatherResult]:
    """Per-unit fused gather: one jit dispatch per co-located device
    group (one per unit in the common case), leaves in caller order —
    the canonical sorted-path order when called from the saver."""
    arrs = [jnp.asarray(a) for a in arrs]
    assert len(arrs) == len(ref_fps) == len(capacities)
    n_blocks = []
    for a in arrs:
        epb = _block_elems(
            jnp.uint8 if a.dtype == jnp.bool_ else a.dtype, block_bytes)
        n_blocks.append(max(1, -(-a.size // epb)))
    caps = [round_capacity(c, nb) for c, nb in zip(capacities, n_blocks)]
    out: List[Optional[GatherResult]] = [None] * len(arrs)
    for idxs in _device_groups(arrs):
        res = _gather_many(
            tuple(arrs[i] for i in idxs),
            tuple(jnp.asarray(ref_fps[i], jnp.uint32) for i in idxs),
            block_bytes=block_bytes,
            n_blocks=tuple(n_blocks[i] for i in idxs),
            capacities=tuple(caps[i] for i in idxs),
            impl=_impl(interpret), quant=quantize_int8)
        for i, r in zip(idxs, res):
            out[i] = GatherResult(*r)
    return out  # type: ignore[return-value]

"""jit'd wrappers: fingerprint arbitrary arrays/pytrees blockwise on device,
compare fingerprint vectors, and gather only dirty blocks for the
device->host transfer.

``interpret=None`` (the default at every production call site) auto-selects
the implementation: the Pallas kernel on TPU, the kernel's own math as one
plain-jnp reduction elsewhere (same integer view, same wrap-around int32
arithmetic, so the checksums are bit-identical — interpret-mode Pallas
would only add compile latency on CPU).  Pass ``interpret=True`` to force
the Pallas kernel through the interpreter (how the property tests exercise
the kernel body off-TPU).
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.block_fp.kernel import (
    block_slabs,
    checksums,
    fingerprint_slabs,
    int_view,
)
from repro.kernels.block_fp.ref import DEFAULT_BLOCK_BYTES, LeafFP

_ROWS = 8  # blocks per grid tile: 8 x 64KiB = 512 KiB of VMEM per input tile


def _impl(interpret: Optional[bool]) -> str:
    if interpret is None:
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return "pallas-interpret" if interpret else "pallas"


def kernel_path(interpret: Optional[bool] = None) -> str:
    """Which path fingerprints a leaf: ``"pallas"`` (the kernel, compiled
    or interpreted) or ``"xla"`` (the plain-jnp reduction)."""
    return "xla" if _impl(interpret) == "jnp" else "pallas"


def _block_elems(dtype, block_bytes: int) -> int:
    itemsize = jnp.dtype(dtype).itemsize
    assert block_bytes % itemsize == 0, (block_bytes, itemsize)
    return block_bytes // itemsize


def _padded(flat: jax.Array, epb: int, pad_rows: bool) -> jax.Array:
    """Zero-pad a flat array to whole blocks (+ whole tiles)."""
    nb = max(1, -(-flat.size // epb))
    if pad_rows:
        nb = -(-nb // _ROWS) * _ROWS
    pad = nb * epb - flat.size
    return jnp.pad(flat, (0, pad)) if pad else flat


def _as_blocks(x: jax.Array, epb: int, pad_rows: bool) -> jax.Array:
    """Flatten and zero-pad to a (n_blocks, epb) view (+ tile padding)."""
    return _padded(x.reshape(-1), epb, pad_rows).reshape(-1, epb)


def _as_slabs(x: jax.Array, block_bytes: int, pad_rows: bool) -> jax.Array:
    """Flatten, take the integer view and zero-pad to the kernels'
    (n_blocks, sub, lanes) slab layout (+ tile padding)."""
    v = int_view(x.reshape(-1))
    epb = _block_elems(v.dtype, block_bytes)
    return block_slabs(_padded(v, epb, pad_rows), epb)


def _fingerprint_jnp(x: jax.Array, block_bytes: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """The kernel's math as one vectorized jnp reduction (non-TPU path)."""
    fp = jnp.concatenate(
        checksums(_as_slabs(x, block_bytes, pad_rows=False)), axis=1)
    vals = _as_blocks(x, _block_elems(x.dtype, block_bytes),
                      pad_rows=False).astype(jnp.float32)
    return (jax.lax.bitcast_convert_type(fp, jnp.uint32),
            jnp.sum(vals * vals, axis=1))


def _fingerprint_pallas(x: jax.Array, slabs: jax.Array, block_bytes: int,
                        impl: str) -> Tuple[jax.Array, jax.Array]:
    """The kernel over ``x``'s tile-padded slabs; dtypes the kernel cannot
    decode get their advisory sumsq from the original values."""
    fp, ss = fingerprint_slabs(slabs, x.dtype, rows_per_tile=_ROWS,
                               interpret=impl == "pallas-interpret")
    if ss is None:
        vals = _as_blocks(x, _block_elems(x.dtype, block_bytes),
                          pad_rows=True).astype(jnp.float32)
        ss = jnp.sum(vals * vals, axis=1)
    return fp, ss


def _fingerprint_one(x, *, block_bytes, n_blocks, impl):
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    if impl == "jnp":
        fp, ss = _fingerprint_jnp(x, block_bytes)
    else:
        fp, ss = _fingerprint_pallas(
            x, _as_slabs(x, block_bytes, pad_rows=True), block_bytes, impl)
    return fp[:n_blocks], ss[:n_blocks]


@functools.partial(jax.jit,
                   static_argnames=("block_bytes", "n_blocks", "impl"))
def _fingerprint(x, *, block_bytes, n_blocks, impl):
    return _fingerprint_one(x, block_bytes=block_bytes, n_blocks=n_blocks,
                            impl=impl)


@functools.partial(jax.jit,
                   static_argnames=("block_bytes", "n_blocks", "impl"))
def _fingerprint_many(xs, *, block_bytes, n_blocks, impl):
    """All of a unit's leaves in ONE dispatch (the save-path hot loop runs
    per unit, not per leaf — on small hosts the dispatch overhead would
    otherwise dwarf the reduction itself).  Besides each leaf's vectors it
    returns their fp vectors concatenated, (sum n_blocks, 2) uint32: the
    table the host fetches in one transfer."""
    out = [_fingerprint_one(x, block_bytes=block_bytes, n_blocks=nb,
                            impl=impl)
           for x, nb in zip(xs, n_blocks)]
    fps = tuple(fp for fp, _ in out)
    return fps, tuple(ss for _, ss in out), jnp.concatenate(fps)


@jax.jit
def _all_fp_equal(cur_fps, ref_fps):
    return jnp.all(jnp.stack([jnp.array_equal(c, r)
                              for c, r in zip(cur_fps, ref_fps)]))


def block_fingerprint(x: jax.Array, *,
                      block_bytes: int = DEFAULT_BLOCK_BYTES,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Per-block (fp (nb, 2) uint32, sumsq (nb,) f32) of ``x``'s bytes."""
    epb = _block_elems(x.dtype, block_bytes)
    n_blocks = max(1, -(-x.size // epb))
    return _fingerprint(x, block_bytes=block_bytes, n_blocks=n_blocks,
                        impl=_impl(interpret))


def _device_groups(arrs) -> List[List[int]]:
    """Indices grouped by the arrays' committed device sets: one jit
    dispatch per co-located group.  A shard-native save hands a
    participant leaves resident on DIFFERENT devices (each block is one
    device's addressable shard) — jitting them together is an error, so
    mixed-device trees dispatch per group (still a single dispatch for
    the ordinary co-located unit)."""
    groups: dict = {}
    for i, a in enumerate(arrs):
        try:
            key = frozenset(d.id for d in a.devices())
        except Exception:  # noqa: BLE001 - non-committed / non-jax arrays
            key = None
        groups.setdefault(key, []).append(i)
    return list(groups.values())


def fingerprint_tree(tree, *, block_bytes: int = DEFAULT_BLOCK_BYTES,
                     interpret: Optional[bool] = None) -> List[LeafFP]:
    """Device fingerprint vectors for every leaf, in canonical (sorted
    path) order — the same order ``serial.flatten_with_paths`` serializes,
    so host tables and device vectors line up index-for-index.  One jit
    dispatch per co-located device group (one per tree in the common
    case); compilations are shared across units of the same structure
    (every stacked block reuses one executable).  Each leaf also carries
    its group's concatenated table (``LeafFP.table``) for
    ``tree_to_host``."""
    from repro.checkpoint.serial import flatten_with_paths

    flat = flatten_with_paths(tree)
    arrs = tuple(jnp.asarray(a) for _, a in flat)
    n_blocks = tuple(
        max(1, -(-a.size // _block_elems(a.dtype, block_bytes)))
        for a in arrs)
    fps: List = [None] * len(arrs)
    sss: List = [None] * len(arrs)
    tables: List = [None] * len(arrs)
    for idxs in _device_groups(arrs):
        f, s, t = _fingerprint_many(
            tuple(arrs[i] for i in idxs), block_bytes=block_bytes,
            n_blocks=tuple(n_blocks[i] for i in idxs), impl=_impl(interpret))
        row = 0
        for i, fp, ss in zip(idxs, f, s):
            fps[i], sss[i], tables[i] = fp, ss, (t, row)
            row += n_blocks[i]
    return [LeafFP(path=path, shape=tuple(a.shape), dtype=str(a.dtype),
                   nbytes=a.size * a.dtype.itemsize,
                   block_bytes=block_bytes, fp=fp, sumsq=ss, table=t)
            for (path, _), a, fp, ss, t in zip(flat, arrs, fps, sss, tables)]


def leaves_match(cur: Sequence[LeafFP], ref: Sequence[LeafFP]) -> bool:
    """True iff every leaf's checksum vector is identical (device compare;
    only the result bits cross to host).  ``ref`` may hold device or host
    (numpy) fingerprints — e.g. a table reloaded from an object envelope
    after a restart.  Mixed-device ``cur`` vectors (sharded saves)
    compare per co-located group."""
    if len(cur) != len(ref):
        return False
    if not all(c.meta_matches(r) for c, r in zip(cur, ref)):
        return False
    cur_fps = [c.fp for c in cur]
    for idxs in _device_groups(cur_fps):
        if not bool(_all_fp_equal(
                tuple(cur_fps[i] for i in idxs),
                tuple(jnp.asarray(ref[i].fp) for i in idxs))):
            return False
    return True


@functools.partial(jax.jit, static_argnames=("block_bytes",))
def _gather(x, idx, *, block_bytes):
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    blocks = _as_blocks(x, _block_elems(x.dtype, block_bytes),
                        pad_rows=False)
    return jnp.take(blocks, idx, axis=0)


def gather_blocks(x: jax.Array, idx: np.ndarray, *,
                  block_bytes: int = DEFAULT_BLOCK_BYTES) -> jax.Array:
    """Device-side gather of the listed blocks: the only payload bytes the
    dirty path ever moves device->host.  Returns (len(idx), elems_per_block)
    in ``x``'s dtype (tail block zero-padded, as fingerprinted)."""
    return _gather(x, jnp.asarray(idx, jnp.int32), block_bytes=block_bytes)


def tree_to_host(leaves: Sequence[LeafFP]) -> List[LeafFP]:
    """Fingerprint vectors as numpy, in ONE batched ``device_get``.

    A leaf that carries its device group's concatenated table
    (``fingerprint_tree``'s output) crosses as part of that table: one
    transfer per group, split on the host by each leaf's block count.
    Its advisory ``sumsq`` stays the device vector (drift scoring reads
    it there; nothing on the host does).  Any other leaf — the fused
    gather's outputs — copies its ``fp`` and ``sumsq``, every copy
    started before the first is waited on."""
    tables = {id(l.table[0]): l.table[0] for l in leaves
              if l.table is not None}
    loose = [(l.fp, l.sumsq) for l in leaves if l.table is None]
    host_tables, host_loose = jax.device_get((tables, loose))
    host_loose = iter(host_loose)
    out = []
    for l in leaves:
        if l.table is not None:
            t, row = l.table
            fp, ss = host_tables[id(t)][row:row + l.n_blocks], l.sumsq
        else:
            fp, ss = next(host_loose)
            ss = None if ss is None else np.asarray(ss)
        out.append(LeafFP(path=l.path, shape=l.shape, dtype=l.dtype,
                          nbytes=l.nbytes, block_bytes=l.block_bytes,
                          fp=np.asarray(fp), sumsq=ss))
    return out

"""Exact per-unit checksums of a state tree, computed on the device.

Every leaf's bits are read as 32-bit words (narrower types widened) and
reduced to two wrap-around sums: the plain sum and a position-weighted
sum, so a changed, lost or moved element changes the pair.  A leaf under
a stacked root (one slice per layer unit) gets one pair per slice.  Two
states hold the same bytes in a unit exactly when their pairs agree
(up to a 2^-64 collision); the comparison reads only a few kilobytes back.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Path = Tuple[str, ...]
_GOLDEN = np.uint32(2654435761)


def leaves_with_paths(tree, prefix: Path = ()):
    """(path, leaf) pairs in sorted-key order."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from leaves_with_paths(tree[k], prefix + (k,))
    else:
        yield prefix, tree


def _words(x: jax.Array) -> jax.Array:
    bits = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    return jax.lax.bitcast_convert_type(x, bits).astype(jnp.uint32)


def _pair(rows: jax.Array) -> jax.Array:
    """rows: (r, n) uint32 -> (r, 2) uint32."""
    pos = jnp.arange(rows.shape[1], dtype=jnp.uint32) * _GOLDEN + 1
    return jnp.stack([jnp.sum(rows, axis=1, dtype=jnp.uint32),
                      jnp.sum(rows * (pos ^ (rows >> 16)), axis=1,
                              dtype=jnp.uint32)], axis=1)


def is_stacked(path: Path, stacked_roots: Sequence[Path]) -> bool:
    """Whether the leaf lies under a stacked root, anywhere after its
    part name (``params/blocks/...``, ``opt/m/blocks/...``)."""
    for root in stacked_roots:
        n = len(root)
        if any(tuple(path[i:i + n]) == tuple(root)
               for i in range(1, 3)):
            return True
    return False


def make_checksum(stacked_roots: Sequence[Path]):
    """A jitted ``tree -> {path: (rows, 2) uint32}``."""
    roots = tuple(tuple(r) for r in stacked_roots)

    @jax.jit
    def checksum(tree):
        out = {}
        for path, x in leaves_with_paths(tree):
            x = jnp.asarray(x)
            if x.ndim == 0:
                x = x.reshape(1)
            lead = x.shape[0] if is_stacked(path, roots) else 1
            out["/".join(path)] = _pair(_words(x).reshape(lead, -1))
        return out

    return checksum


def to_host(sums) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v) for k, v in jax.device_get(sums).items()}


def mismatches(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]
               ) -> int:
    """Number of (leaf, slice) pairs that differ or are missing."""
    bad = 0
    for k, w in want.items():
        g = got.get(k)
        if g is None or g.shape != w.shape:
            bad += w.shape[0]
            continue
        bad += int(np.sum(np.any(g != w, axis=1)))
    return bad

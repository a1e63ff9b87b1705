"""Oracle for the block fingerprint kernel: the numpy implementation the
checkpoint store uses to re-verify fingerprints on read.

The fingerprint of a buffer is defined over its raw little-endian bytes,
independent of dtype: the buffer is zero-padded to a whole number of
``block_bytes`` blocks, viewed as uint32 words, and each block yields a
Fletcher-style pair computed in wrap-around uint32 arithmetic:

    fp1[b] = sum(words[b])                 mod 2**32
    fp2[b] = sum((i + 1) * words[b][i])    mod 2**32

Integer arithmetic makes the pair bit-reproducible between the Pallas
kernel (device) and this oracle (host) — float reductions would not be.
The advisory per-block sum-of-squares (drift scoring only, never hashed or
compared for equality) IS a float reduction and is therefore excluded from
digests and dedup decisions.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import numpy as np

DEFAULT_BLOCK_BYTES = 65536  # 64 KiB — the dedup/transfer granularity


@dataclasses.dataclass
class LeafFP:
    """Per-leaf fingerprint vector (device jax arrays or host numpy)."""
    path: str
    shape: Tuple[int, ...]
    dtype: str            # str(np/jnp dtype), e.g. "bfloat16"
    nbytes: int           # unpadded byte length of the leaf
    block_bytes: int
    fp: Any               # (n_blocks, 2) uint32 — hashed and compared
    sumsq: Optional[Any]  # (n_blocks,) float32 — advisory (drift scoring)
    # Device vectors from ``fingerprint_tree`` only: the leaf's device
    # group's fp vectors concatenated on device, (sum n_blocks, 2) uint32,
    # and this leaf's first row in it — the group's tables cross to host
    # as one array (``tree_to_host``).
    table: Optional[Tuple[Any, int]] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def n_blocks(self) -> int:
        return int(self.fp.shape[0])

    def meta_matches(self, other: "LeafFP") -> bool:
        return (self.path == other.path
                and tuple(self.shape) == tuple(other.shape)
                and self.dtype == other.dtype
                and self.nbytes == other.nbytes
                and self.block_bytes == other.block_bytes)


def fingerprint_bytes(raw: bytes, block_bytes: int = DEFAULT_BLOCK_BYTES
                      ) -> np.ndarray:
    """(n_blocks, 2) uint32 fingerprint pairs of ``raw``."""
    assert block_bytes % 4 == 0, block_bytes
    n = len(raw)
    nb = max(1, -(-n // block_bytes))
    buf = np.zeros(nb * block_bytes, np.uint8)
    buf[:n] = np.frombuffer(raw, np.uint8)
    words = buf.view("<u4").reshape(nb, block_bytes // 4)
    weights = np.arange(1, words.shape[1] + 1, dtype=np.uint32)
    fp1 = np.sum(words, axis=1, dtype=np.uint32)
    # element-wise uint32 multiply wraps mod 2**32, matching the device
    fp2 = np.sum(words * weights, axis=1, dtype=np.uint32)
    return np.stack([fp1, fp2], axis=1)


def fingerprint_array(arr: np.ndarray,
                      block_bytes: int = DEFAULT_BLOCK_BYTES,
                      *, with_sumsq: bool = True) -> LeafFP:
    """Host-side LeafFP of a numpy array (fp exact, sumsq advisory).

    ``with_sumsq=False`` skips the advisory float reduction — callers
    that only need the hashed integer pairs (read-time verification)
    save a full-data cast + square + sum."""
    arr = np.ascontiguousarray(arr)
    raw = arr.tobytes()
    fp = fingerprint_bytes(raw, block_bytes)
    itemsize = arr.dtype.itemsize
    epb = block_bytes // itemsize if block_bytes % itemsize == 0 else None
    sumsq = None
    if epb and with_sumsq:
        flat = np.asarray(arr, np.float32).reshape(-1)
        pad = epb if flat.size == 0 else (-flat.size) % epb
        if pad:
            flat = np.concatenate([flat, np.zeros(pad, np.float32)])
        blocks = flat.reshape(-1, epb)[: fp.shape[0]]
        sumsq = np.sum(np.square(blocks), axis=1)
    return LeafFP(path="", shape=tuple(arr.shape), dtype=str(arr.dtype),
                  nbytes=len(raw), block_bytes=block_bytes, fp=fp,
                  sumsq=sumsq)


def dirty_block_indices(cur: LeafFP, ref: Optional[LeafFP]) -> np.ndarray:
    """Indices of blocks whose fingerprints differ (all blocks when there is
    no comparable reference)."""
    cfp = np.asarray(cur.fp)
    if ref is None or not cur.meta_matches(ref):
        return np.arange(cfp.shape[0])
    return np.flatnonzero(np.any(cfp != np.asarray(ref.fp), axis=1))

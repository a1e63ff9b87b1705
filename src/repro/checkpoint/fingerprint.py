"""Host-side fingerprint plumbing for the block-sparse checkpoint path.

The device kernel (``repro.kernels.block_fp``) reduces a unit's tensors to
per-64KiB-block checksum pairs.  This module turns those vectors into:

- a canonical **fingerprint table** blob (msgpack of per-leaf metadata +
  checksum bytes, sorted leaf order), and its blake2b **fp digest** — the
  content address of fingerprint-pipeline objects.  Two units hash to the
  same digest iff their fingerprint tables match, so an unchanged re-save
  dedups with zero payload transfer and zero payload hashing: the digest
  costs one blake2b over ~0.02% of the data.
- **FingerprintPacket**: what the saver hands the chunk store — per-leaf
  dirty block indices + gathered block bytes (delta path) or the full raw
  bytes (full path), plus the table blob.
- reconstruction + verification: patch dirty blocks onto a base tree and
  re-derive the fp digest from the rebuilt tensors (the read-side
  integrity check for fp-addressed objects, replacing the canonical-payload
  blake2b used by v1 objects).

The digest hashes ONLY integer checksums and leaf metadata — never float
reductions — so write-time (device) and read-time (host oracle) derivations
are bit-identical.  See docs/perf.md for the pipeline end to end.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict, List, Optional, Sequence

import msgpack
import numpy as np

from repro.checkpoint.compression import np_dtype as _np_dtype
from repro.kernels.block_fp.ref import (
    DEFAULT_BLOCK_BYTES,
    LeafFP,
    dirty_block_indices,
    fingerprint_array,
)

PyTree = Any

TABLE_VERSION = 1
DIGEST_BYTES = 20  # same width as the canonical-payload digests

#: per-event ``last_save_stats`` counters of the leaves fingerprinted and
#: gathered on device, by the path that ran them: the Pallas kernels
#: (``block_fp`` / ``block_gather``) or plain XLA ops
KERNEL_LEAF_KEYS = ("fp_leaves_pallas", "fp_leaves_xla",
                    "gather_leaves_pallas", "gather_leaves_xla")


# ------------------------------------------------------------------- tables
def pack_table(leaves: Sequence[LeafFP]) -> bytes:
    """Canonical fingerprint-table blob (deterministic: fixed field order,
    sorted-leaf order is the caller's contract, checksums as LE bytes)."""
    rows = []
    for l in leaves:
        fp = np.ascontiguousarray(np.asarray(l.fp, dtype="<u4"))
        rows.append([l.path, list(l.shape), l.dtype, int(l.nbytes),
                     int(l.block_bytes), fp.tobytes()])
    return msgpack.packb({"v": TABLE_VERSION, "leaves": rows},
                         use_bin_type=True)


def unpack_table(blob: bytes) -> List[LeafFP]:
    d = msgpack.unpackb(blob, raw=False)
    if not isinstance(d, dict) or d.get("v") != TABLE_VERSION:
        raise ValueError("bad fingerprint table blob")
    out = []
    for path, shape, dtype, nbytes, block_bytes, fp_bytes in d["leaves"]:
        fp = np.frombuffer(fp_bytes, "<u4").reshape(-1, 2).astype(np.uint32)
        out.append(LeafFP(path=path, shape=tuple(shape), dtype=dtype,
                          nbytes=nbytes, block_bytes=block_bytes, fp=fp,
                          sumsq=None))
    return out


def fp_digest(table_blob: bytes) -> str:
    return hashlib.blake2b(table_blob, digest_size=DIGEST_BYTES).hexdigest()


def table_of_tree(tree: PyTree,
                  block_bytes: int = DEFAULT_BLOCK_BYTES) -> List[LeafFP]:
    """Host (numpy oracle) fingerprint table of a decoded tree — used by
    the store to verify fp-addressed objects on read.  Skips the advisory
    sumsq reduction: only the integer pairs are hashed/compared, and the
    restore hot path calls this once per fp object."""
    from repro.checkpoint.serial import flatten_with_paths

    out = []
    for path, arr in flatten_with_paths(tree):
        leaf = fingerprint_array(np.asarray(arr), block_bytes,
                                 with_sumsq=False)
        leaf.path = path
        out.append(leaf)
    return out


def meta_table(tree: PyTree,
               block_bytes: int = DEFAULT_BLOCK_BYTES) -> List[LeafFP]:
    """Metadata-only table of a (possibly device-resident) tree: paths,
    shapes, dtypes, byte lengths — with ZEROED checksum vectors and no
    data movement at all.  Exactly enough for ``meta_matches``-based
    planning: the overlapped saver picks delta bases and predicts gather
    capacities from structure alone, before any fingerprint has crossed
    to host.  Never pack or hash one."""
    from repro.checkpoint.serial import flatten_with_paths

    out = []
    for path, arr in flatten_with_paths(tree):
        dtype = str(arr.dtype)
        itemsize = _np_dtype(dtype).itemsize
        size = 1
        for d in arr.shape:
            size *= int(d)
        nbytes = size * itemsize
        nb = max(1, -(-nbytes // block_bytes))
        out.append(LeafFP(path=path, shape=tuple(arr.shape), dtype=dtype,
                          nbytes=nbytes, block_bytes=block_bytes,
                          fp=np.zeros((nb, 2), np.uint32), sumsq=None))
    return out


# ------------------------------------------------------------------ packets
@dataclasses.dataclass
class LeafPayload:
    """One leaf's contribution to a write: either the full raw bytes
    (``idx is None``) or the gathered dirty blocks (padded to whole
    blocks, ``idx`` listing their positions).

    ``data`` may be a zero-copy ``memoryview`` into a pinned staging
    slot (the overlapped saver's ``async_io.StagingArena``); the chunk
    store materializes ``bytes`` on the writer thread, and the slot is
    only recycled after the unit's write resolves."""
    path: str
    shape: tuple
    dtype: str
    nbytes: int
    block_bytes: int
    idx: Optional[np.ndarray]
    data: "bytes | memoryview"


@dataclasses.dataclass
class FingerprintPacket:
    """Everything the chunk store needs to persist one unit without ever
    seeing the full canonical payload on the dirty path."""
    digest: str               # fp digest (content address)
    table: bytes              # packed fingerprint table
    leaves: List[LeafPayload]
    full: bool                # True -> every leaf carries its full bytes
    base_digest: Optional[str] = None  # required when not full
    logical_bytes: int = 0    # sum of unpadded leaf bytes (accounting)


# ------------------------------------------------------- rebuild and verify
def _leaf_array(raw: bytes, shape, dtype: str) -> np.ndarray:
    return np.frombuffer(raw, dtype=_np_dtype(dtype)).reshape(shape).copy()


def rebuild_full(leaves: Sequence[LeafPayload]) -> PyTree:
    from repro.checkpoint.serial import unflatten_from_paths

    items = {l.path: _leaf_array(l.data[:l.nbytes], l.shape, l.dtype)
             for l in leaves}
    return unflatten_from_paths(items)


def patch_tree(base_tree: PyTree, records: List[Dict[str, Any]]) -> PyTree:
    """Overlay dirty blocks from a block-delta payload onto the base tree.

    Unlisted leaves (and unlisted blocks) keep the base content — the
    whole point: a clean block never existed in the delta object."""
    from repro.checkpoint.serial import (flatten_with_paths,
                                         unflatten_from_paths)

    base = {p: np.asarray(a) for p, a in flatten_with_paths(base_tree)}
    for rec in records:
        path = rec["name"]
        if path not in base:
            raise KeyError(f"block-delta patches unknown leaf {path!r}")
        block = rec["block"]
        nbytes = rec["nbytes"]
        nb = max(1, -(-nbytes // block))
        buf = np.zeros(nb * block, np.uint8)
        raw = np.ascontiguousarray(base[path]).view(np.uint8).reshape(-1)
        if raw.size != nbytes:
            raise ValueError(
                f"base leaf {path!r} has {raw.size} bytes, delta expects "
                f"{nbytes}")
        buf[:nbytes] = raw
        data = np.frombuffer(rec["data"], np.uint8)
        for j, bi in enumerate(rec["idx"]):
            buf[bi * block:(bi + 1) * block] = data[j * block:(j + 1) * block]
        base[path] = _leaf_array(buf[:nbytes].tobytes(), rec["shape"],
                                 rec["dtype"])
    return unflatten_from_paths(base)


def verify_tree_digest(tree: PyTree, digest: str,
                       block_bytes: int) -> bool:
    """Recompute the fp digest of a reconstructed tree (host oracle)."""
    return fp_digest(pack_table(table_of_tree(tree, block_bytes))) == digest

"""Pure, import-light worker functions for the checkpoint IO stack.

This module is the *extraction target* of the process-backed IO refactor:
every CPU-heavy byte transform the checkpoint pipeline runs — blake2
hashing, zstd compression, per-tensor record codecs, XOR (XD01) and
block-sparse (BD02) delta codecs, object-envelope decode/verify, and
atomic file IO — lives here as a plain function over plain values
(bytes, str, int, list, dict).  ``compression.py`` and ``serial.py``
delegate their implementations to this module, so the thread backend and
the process backend execute the *same code* and stay bit-identical.

Import rules (load-bearing, see the bootstrap in ``async_io.py``):

- stdlib + ``numpy`` + ``msgpack`` only, plus the *optional*
  ``zstandard`` / ``ml_dtypes`` imports the codecs already tolerated.
- **Never** ``repro.*``: subprocess workers load this file by path
  (``importlib.util.spec_from_file_location``) precisely so they skip
  the ``repro.checkpoint`` package ``__init__`` — whose import chain
  (chunk_store → fingerprint → kernels) pulls in jax.  A worker process
  must never import jax: it would pay seconds of import time and could
  fight the parent for accelerator state.

Worker protocol (``worker_main``): the parent sends pickled
``(fn_id, args, resp_spec)`` tasks over stdin; payload-sized ``bytes``
args arrive as ``(SHM_MARK, name, length)`` references into
parent-owned ``multiprocessing.shared_memory`` blocks (read directly
from ``/dev/shm`` so the child's resource tracker never learns about —
and can never unlink — parent segments).  Results or ``("err", kind,
message, traceback)`` tuples go back over stdout; when ``resp_spec =
(scratch_name, min_bytes)`` is set, payload-sized ``bytes`` INSIDE a
result are written into this worker's persistent
``/dev/shm/<scratch_name>`` scratch file and replaced by ``(SHM_MARK,
offset, length)`` markers (the pipe is a syscall-heavy copy path — a
restore returning tens of MB of decoded tensors through a 64 KiB pipe
buffer is what the staging avoids; a persistent per-worker scratch
keeps tmpfs pages allocated across calls instead of paying
create/fault/unlink churn per response).  Only builtin types cross
the pipe: this module is imported
under *different module names* in parent and child, so pickling
classes defined here would force the receiving side to import the
other side's module name.

``fingerprint_pairs`` intentionally duplicates the ~10-line numpy oracle
in ``repro.kernels.block_fp.ref`` (importing it from here would drag the
jax-importing kernels package into workers); the conformance suite pins
the two implementations bit-equal.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import sys
import threading
import time
import traceback
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import msgpack
import numpy as np

try:  # optional dependency: the repo must import (and run) without zstd
    import zstandard as _zstd
    HAVE_ZSTD = True
except ImportError:  # pragma: no cover - depends on environment
    _zstd = None
    HAVE_ZSTD = False

ZSTD_LEVEL = 3
QUANT_BLOCK = 256

# Wire/framing constants shared with serial.py / chunk_store.py /
# fingerprint.py (re-declared here so this file stands alone in a child).
CHUNK_FORMAT_VERSION = 1   # serial.FORMAT_VERSION
OBJECT_VERSION = 1         # chunk_store.OBJECT_VERSION
TABLE_VERSION = 1          # fingerprint.TABLE_VERSION
DIGEST_BYTES = 20          # blake2b-160
DEFAULT_BLOCK_BYTES = 65536

DELTA_MAGIC = b"XD01"
# Non-zero XOR runs closer than this are merged into one segment: the
# per-segment overhead (offset + length framing) outweighs a few zero bytes.
DELTA_MERGE_GAP = 32
BLOCK_DELTA_MAGIC = b"BD02"

SHM_DIR = "/dev/shm"
SHM_MARK = "__repro_shm__"

#: What a payload may arrive as: ``bytes``, or a zero-copy flat uint8
#: view of an array's bytes (the saver's fetched leaves), whose ``len``
#: is its size in bytes.
Buffer = Union[bytes, bytearray, memoryview]


class CodecUnavailable(RuntimeError):
    """A codec was explicitly requested but its dependency is missing."""


class CorruptObject(RuntimeError):
    """Worker-side integrity failure.  The dispatch layer re-raises it as
    ``serial.ChunkCorruption`` in the parent so restore's fallback
    machinery treats thread- and process-backend corruption alike."""


# --------------------------------------------------------------- zstd state
def default_codec() -> str:
    """Best available lossless codec for this environment."""
    return "zstd" if HAVE_ZSTD else "none"


def resolve_codec(codec: Optional[str]) -> str:
    """Map the "auto"/None sentinel to the environment default."""
    if codec is None or codec == "auto":
        return default_codec()
    return codec


def _require_zstd() -> None:
    if not HAVE_ZSTD:
        raise CodecUnavailable(
            "codec 'zstd' requires the optional 'zstandard' package "
            "(pip install zstandard); use codec='auto' or 'none' instead")


# zstd (de)compression contexts are NOT thread-safe; the async writer pool
# (and each worker process) compresses concurrently, so contexts are
# per-thread — and, trivially, per-process.
_tls = threading.local()


def _cctx():
    _require_zstd()
    c = getattr(_tls, "cctx", None)
    if c is None:
        c = _tls.cctx = _zstd.ZstdCompressor(level=ZSTD_LEVEL)
    return c


def _dctx():
    _require_zstd()
    d = getattr(_tls, "dctx", None)
    if d is None:
        d = _tls.dctx = _zstd.ZstdDecompressor()
    return d


def zstd_compress(raw: bytes) -> bytes:
    return _cctx().compress(raw)


def zstd_decompress(blob: bytes) -> bytes:
    return _dctx().decompress(blob)


def _to_bytes(arr: np.ndarray) -> bytes:
    return np.ascontiguousarray(arr).tobytes()


def np_dtype(dtype: str) -> np.dtype:
    """Serialized dtype string -> numpy dtype (ml_dtypes extras included).
    The single mapping both the codec decoder and the fingerprint rebuild
    path use — extend here when the serializer learns a new dtype."""
    if dtype == "bfloat16":
        import ml_dtypes  # jax dependency; provides bfloat16 for numpy
        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(dtype)


def blake2_hex(blob: bytes, digest_size: int = DIGEST_BYTES) -> str:
    return hashlib.blake2b(blob, digest_size=digest_size).hexdigest()


# ----------------------------------------------------------- tensor codecs
def quantize_int8(arr: np.ndarray, block: int = QUANT_BLOCK
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Blockwise symmetric quantization of the flattened array.
    Returns (int8 values, f32 scales per block)."""
    flat = np.asarray(arr, dtype=np.float32).reshape(-1)
    pad = (-len(flat)) % block
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.float32)])
    blocks = flat.reshape(-1, block)
    scales = np.max(np.abs(blocks), axis=1, keepdims=True) / 127.0
    scales = np.where(scales == 0, 1.0, scales)
    q = np.clip(np.rint(blocks / scales), -127, 127).astype(np.int8)
    return q.reshape(-1), scales.astype(np.float32).reshape(-1)


def dequantize_int8(q: np.ndarray, scales: np.ndarray, size: int,
                    block: int = QUANT_BLOCK) -> np.ndarray:
    blocks = q.astype(np.float32).reshape(-1, block)
    out = blocks * scales.reshape(-1, 1)
    return out.reshape(-1)[:size]


def _lossless(raw: bytes) -> Tuple[bytes, str]:
    """Compress with the best available lossless codec."""
    if HAVE_ZSTD:
        return _cctx().compress(raw), "zstd"
    return raw, "none"


def encode(arr: np.ndarray, codec: str) -> Tuple[bytes, str, Optional[Dict]]:
    """Returns (payload, codec_used, extra_meta)."""
    arr = np.asarray(arr)
    codec = resolve_codec(codec)
    if codec == "none":
        return _to_bytes(arr), "none", None
    if codec == "zstd":
        return _cctx().compress(_to_bytes(arr)), "zstd", None
    if codec == "int8":
        # Only sensible for float weight tensors of meaningful size.
        if arr.dtype.kind != "f" and str(arr.dtype) != "bfloat16":
            blob, used = _lossless(_to_bytes(arr))
            return blob, used, None
        if arr.size < QUANT_BLOCK:
            blob, used = _lossless(_to_bytes(arr))
            return blob, used, None
        q, scales = quantize_int8(arr)
        blob, comp = _lossless(q.tobytes() + scales.tobytes())
        return (blob, "int8",
                {"n_q": int(q.size), "n_scale": int(scales.size),
                 "block": QUANT_BLOCK, "comp": comp})
    raise ValueError(f"unknown codec {codec!r}")


def decode(payload: bytes, codec: str, *, shape, dtype,
           extra: Optional[Dict] = None) -> np.ndarray:
    out_dtype = np_dtype(dtype)
    if codec == "none":
        return np.frombuffer(payload, dtype=out_dtype).reshape(shape).copy()
    if codec == "zstd":
        raw = _dctx().decompress(payload)
        return np.frombuffer(raw, dtype=out_dtype).reshape(shape).copy()
    if codec == "int8":
        # chunks written before the optional-zstd split always compressed
        comp = (extra or {}).get("comp", "zstd")
        raw = _dctx().decompress(payload) if comp == "zstd" else payload
        n_q, n_scale = extra["n_q"], extra["n_scale"]
        q = np.frombuffer(raw[:n_q], dtype=np.int8)
        scales = np.frombuffer(raw[n_q:n_q + 4 * n_scale], dtype=np.float32)
        size = int(np.prod(shape)) if shape else 1
        out = dequantize_int8(q, scales, size, extra.get("block", QUANT_BLOCK))
        return out.astype(out_dtype).reshape(shape)
    raise ValueError(f"unknown codec {codec!r}")


def encode_record(raw: Buffer, shape, dtype: str, codec: str
                  ) -> Tuple[Buffer, str, Optional[Dict]]:
    """Per-tensor encode from raw little-endian bytes (bit-identical to
    ``encode`` on the equivalent array; the int8 path rebuilds it).
    ``raw`` may be any byte buffer; codec ``none`` returns it as it is."""
    codec = resolve_codec(codec)
    if codec == "none":
        return raw, "none", None
    if codec == "zstd":
        return _cctx().compress(raw), "zstd", None
    arr = np.frombuffer(raw, dtype=np_dtype(dtype)).reshape(tuple(shape))
    return encode(arr, codec)


def decode_record(data: bytes, codec: str, shape, dtype: str,
                  extra: Optional[Dict] = None) -> bytes:
    """Per-tensor decode to raw little-endian bytes of the output dtype."""
    if codec == "none":
        return bytes(data)
    if codec == "zstd":
        return _dctx().decompress(data)
    return _to_bytes(decode(data, codec, shape=tuple(shape), dtype=dtype,
                            extra=extra))


# --------------------------------------------------------------- delta codec
def delta_encode(cur: bytes, base: bytes, *, gap: int = DELTA_MERGE_GAP,
                 compress: Optional[str] = None) -> bytes:
    """Sparse bytewise XOR diff of ``cur`` against ``base``.

    ``base`` is zero-padded/truncated to ``len(cur)`` so payloads of
    different lengths still diff (the tail past ``base`` XORs with zeros,
    i.e. is stored verbatim).  The result decodes with ``delta_decode``
    against the same ``base``.
    """
    n = len(cur)
    a = np.frombuffer(cur, np.uint8)
    if len(base) >= n:
        b = np.frombuffer(base, np.uint8, count=n)
    else:
        b = np.zeros(n, np.uint8)
        b[:len(base)] = np.frombuffer(base, np.uint8)
    x = a ^ b
    nz = np.flatnonzero(x)
    segs = []
    if nz.size:
        brk = np.flatnonzero(np.diff(nz) > gap)
        starts = nz[np.concatenate([[0], brk + 1])]
        ends = nz[np.concatenate([brk, [nz.size - 1]])] + 1
        segs = [[int(s), x[s:e].tobytes()] for s, e in zip(starts, ends)]
    body = msgpack.packb({"n": n, "segs": segs}, use_bin_type=True)
    comp = resolve_codec(compress)
    if comp == "zstd":
        return DELTA_MAGIC + b"\x01" + _cctx().compress(body)
    return DELTA_MAGIC + b"\x00" + body


def delta_decode(blob: bytes, base: bytes) -> bytes:
    """Reconstruct the payload ``delta_encode`` diffed against ``base``."""
    if blob[:4] != DELTA_MAGIC:
        raise ValueError("not a delta blob (bad magic)")
    body = blob[5:]
    if blob[4] == 1:
        body = _dctx().decompress(body)
    d = msgpack.unpackb(body, raw=False)
    n = d["n"]
    out = np.zeros(n, np.uint8)
    m = min(n, len(base))
    out[:m] = np.frombuffer(base, np.uint8, count=m)
    for off, data in d["segs"]:
        seg = np.frombuffer(data, np.uint8)
        out[off:off + len(seg)] ^= seg
    return out.tobytes()


def is_delta(blob: bytes) -> bool:
    return blob[:4] == DELTA_MAGIC


# -------------------------------------------------- block-sparse delta (v2)
def block_delta_encode(records: List[Dict], *,
                       compress: Optional[str] = None) -> bytes:
    """Frame per-leaf dirty-block records as a v2 block-sparse delta blob.

    Each record: {"name", "shape", "dtype", "nbytes", "block",
    "idx": [block indices], "data": concatenated block-sized chunks}.
    Blocks are full ``block``-sized slices (the tail block zero-padded,
    exactly as fingerprinted), so decode is pure slice assignment.
    """
    rows = [[r["name"], list(r["shape"]), r["dtype"], int(r["nbytes"]),
             int(r["block"]), [int(i) for i in r["idx"]], r["data"]]
            for r in records]
    body = msgpack.packb({"v": 1, "tensors": rows}, use_bin_type=True)
    comp = resolve_codec(compress)
    if comp == "zstd":
        return BLOCK_DELTA_MAGIC + b"\x01" + _cctx().compress(body)
    return BLOCK_DELTA_MAGIC + b"\x00" + body


def block_delta_decode(blob: bytes) -> List[Dict]:
    if blob[:4] != BLOCK_DELTA_MAGIC:
        raise ValueError("not a block-delta blob (bad magic)")
    body = blob[5:]
    if blob[4] == 1:
        body = _dctx().decompress(body)
    d = msgpack.unpackb(body, raw=False)
    if not isinstance(d, dict) or d.get("v") != 1:
        raise ValueError("bad block-delta body")
    return [{"name": name, "shape": shape, "dtype": dtype, "nbytes": nbytes,
             "block": block, "idx": idx, "data": data}
            for name, shape, dtype, nbytes, block, idx, data in d["tensors"]]


def is_block_delta(blob: bytes) -> bool:
    return blob[:4] == BLOCK_DELTA_MAGIC


# ---------------------------------------------------------- msgpack framing
# A stored object is msgpack.  Its bulk ``bin`` values are framed here by
# hand, around the buffers that hold them, so the object is copied once,
# in one join (``msgpack.Packer`` writes map and array headers, but has no
# ``bin`` header of its own).

def bin_header(n: int) -> bytes:
    """The msgpack header of an ``n``-byte ``bin``: the smallest of bin8,
    bin16 and bin32 that holds ``n``, as ``packb`` chooses."""
    if n < 0x100:
        return b"\xc4" + n.to_bytes(1, "big")
    if n < 0x10000:
        return b"\xc5" + n.to_bytes(2, "big")
    if n < 0x100000000:
        return b"\xc6" + n.to_bytes(4, "big")
    raise ValueError(f"a msgpack bin holds under 4 GiB, not {n} bytes")


def frame_object(env: Dict[str, Any]) -> List[Buffer]:
    """Pieces whose join is ``msgpack.packb(env, use_bin_type=True)``,
    with ``env["payload"]`` given as a buffer or as a list of pieces (the
    payload is their join): every key in ``env``'s order, the payload's
    pieces by reference."""
    pk = msgpack.Packer(use_bin_type=True)
    head = [pk.pack_map_header(len(env))]
    pieces: List[Buffer] = []
    for k, v in env.items():
        head.append(pk.pack(k))
        if k != "payload":
            head.append(pk.pack(v))
            continue
        parts = v if isinstance(v, list) else [v]
        head.append(bin_header(sum(len(p) for p in parts)))
        pieces.append(b"".join(head))
        pieces += parts
        head = []
    if head:
        pieces.append(b"".join(head))
    return pieces


# ------------------------------------------------------ chunk payload level
# ``items`` is the flat wire form of a tensor tree: a list of
# (name, shape, dtype, raw_le_bytes) tuples in flatten order.  It is the
# only tensor currency that crosses the worker pipe — never arrays, never
# pytrees.

Items = List[Tuple[str, Sequence[int], str, Buffer]]


def encode_chunk_pieces(items: Items, meta: Dict[str, Any],
                        codec: str) -> List[Buffer]:
    """Chunk payload from flat items, as pieces whose join is the blob
    ``msgpack.packb`` gives for ``{"version", "meta", "tensors"}``: the
    framing in small ``bytes``, each record's encoded data by reference
    (under codec ``none``, the item's own buffer), so the payload is
    copied only where the pieces are joined."""
    pk = msgpack.Packer(use_bin_type=True)
    head = [pk.pack_map_header(3), pk.pack("version"),
            pk.pack(CHUNK_FORMAT_VERSION), pk.pack("meta"), pk.pack(meta),
            pk.pack("tensors"), pk.pack_array_header(len(items))]
    pieces: List[Buffer] = []
    for name, shape, dtype, raw in items:
        data, used, extra = encode_record(raw, shape, dtype, codec)
        fields = {"name": name, "shape": list(shape), "dtype": dtype,
                  "codec": used, "crc": zlib.crc32(data) & 0xFFFFFFFF,
                  "extra": extra}
        head.append(pk.pack_map_header(len(fields) + 1))
        for k, v in fields.items():
            head += [pk.pack(k), pk.pack(v)]
        head += [pk.pack("data"), bin_header(len(data))]
        pieces += [b"".join(head), data]
        head = []
    if head:
        pieces.append(b"".join(head))
    return pieces


def encode_chunk_items(items: Items, meta: Dict[str, Any],
                       codec: str) -> bytes:
    """Chunk payload blob from flat items (the single implementation
    behind ``serial.encode_chunk``)."""
    return b"".join(encode_chunk_pieces(items, meta, codec))


def decode_chunk_items(blob: bytes, verify: bool = True
                       ) -> Tuple[Dict, Items]:
    """(meta, items) of a chunk payload blob, with per-record CRC checks
    (the single implementation behind ``serial.decode_chunk``)."""
    try:
        payload = msgpack.unpackb(blob, raw=False)
    except Exception as e:  # noqa: BLE001 - msgpack raises many types
        raise CorruptObject(f"unreadable chunk payload: {e!r}") from e
    if not isinstance(payload, dict) \
            or payload.get("version") != CHUNK_FORMAT_VERSION:
        ver = payload.get("version") if isinstance(payload, dict) else None
        raise CorruptObject(f"bad chunk version {ver}")
    items: Items = []
    for t in payload["tensors"]:
        if verify and (zlib.crc32(t["data"]) & 0xFFFFFFFF) != t["crc"]:
            raise CorruptObject(f"crc mismatch for tensor {t['name']}")
        raw = decode_record(t["data"], t["codec"], t["shape"], t["dtype"],
                            t.get("extra"))
        items.append((t["name"], tuple(t["shape"]), t["dtype"], raw))
    return payload["meta"], items


# --------------------------------------------------------- fingerprint side
def fingerprint_pairs(raw: bytes, block_bytes: int = DEFAULT_BLOCK_BYTES
                      ) -> np.ndarray:
    """(n_blocks, 2) uint32 Fletcher-style fingerprint pairs of ``raw``.

    Intentional duplicate of ``repro.kernels.block_fp.ref
    .fingerprint_bytes`` (see module docstring); the conformance suite
    asserts the two stay bit-identical."""
    assert block_bytes % 4 == 0, block_bytes
    n = len(raw)
    nb = max(1, -(-n // block_bytes))
    buf = np.zeros(nb * block_bytes, np.uint8)
    buf[:n] = np.frombuffer(raw, np.uint8)
    words = buf.view("<u4").reshape(nb, block_bytes // 4)
    weights = np.arange(1, words.shape[1] + 1, dtype=np.uint32)
    fp1 = np.sum(words, axis=1, dtype=np.uint32)
    fp2 = np.sum(words * weights, axis=1, dtype=np.uint32)
    return np.stack([fp1, fp2], axis=1)


def _unpack_fp_rows(blob: bytes) -> List[list]:
    """Raw rows of a packed fingerprint table:
    [path, shape, dtype, nbytes, block_bytes, fp_le_bytes]."""
    try:
        d = msgpack.unpackb(blob, raw=False)
    except Exception as e:  # noqa: BLE001
        raise CorruptObject(f"bad fingerprint table blob: {e!r}") from e
    if not isinstance(d, dict) or d.get("v") != TABLE_VERSION:
        raise CorruptObject("bad fingerprint table blob")
    return d["leaves"]


def verify_fp_items(digest: str, fp_blob: bytes, items: Items, *,
                    check_content: bool = True) -> None:
    """Read-side integrity check of an fp-addressed object: the table
    must hash to the digest, and (``check_content``) the fingerprint
    pairs recomputed from the reconstructed leaf bytes must match the
    stored table — same semantics as ``ChunkStore._tree_from_fp_env``'s
    ``pack_table(table_of_tree(...)) != env["fp"]`` comparison, keyed by
    leaf path so it is order-insensitive."""
    rows = _unpack_fp_rows(fp_blob)
    if blake2_hex(fp_blob) != digest:
        raise CorruptObject(f"fingerprint digest mismatch for {digest}")
    if not check_content:
        return
    want = {path: (tuple(shape), dtype, int(nbytes), int(block), fp)
            for path, shape, dtype, nbytes, block, fp in rows}
    got = {name: (tuple(shape), dtype, raw)
           for name, shape, dtype, raw in items}
    if set(want) != set(got):
        raise CorruptObject(
            f"fingerprint mismatch for reconstructed {digest}")
    for path, (shape, dtype, nbytes, block, fp) in want.items():
        g_shape, g_dtype, raw = got[path]
        if (g_shape, g_dtype, len(raw)) != (shape, dtype, nbytes):
            raise CorruptObject(
                f"fingerprint mismatch for reconstructed {digest}")
        pairs = np.ascontiguousarray(
            fingerprint_pairs(raw, block).astype("<u4"))
        if pairs.tobytes() != fp:
            raise CorruptObject(
                f"fingerprint mismatch for reconstructed {digest}")


def patch_items(base_items: Items, records: List[Dict]) -> Items:
    """Overlay dirty blocks from a block-delta payload onto base items —
    the pure-bytes mirror of ``fingerprint.patch_tree``.  Unlisted
    leaves (and unlisted blocks) keep the base content."""
    out: Dict[str, list] = {name: [shape, dtype, raw]
                            for name, shape, dtype, raw in base_items}
    for rec in records:
        path = rec["name"]
        if path not in out:
            raise CorruptObject(
                f"block-delta patches unknown leaf {path!r}")
        block = int(rec["block"])
        nbytes = int(rec["nbytes"])
        raw = out[path][2]
        if len(raw) != nbytes:
            raise CorruptObject(
                f"base leaf {path!r} has {len(raw)} bytes, delta expects "
                f"{nbytes}")
        nb = max(1, -(-nbytes // block))
        buf = np.zeros(nb * block, np.uint8)
        buf[:nbytes] = np.frombuffer(raw, np.uint8)
        data = np.frombuffer(rec["data"], np.uint8)
        for j, bi in enumerate(rec["idx"]):
            buf[bi * block:(bi + 1) * block] = \
                data[j * block:(j + 1) * block]
        out[path] = [tuple(rec["shape"]), rec["dtype"],
                     buf[:nbytes].tobytes()]
    return [(name, tuple(v[0]), v[1], v[2]) for name, v in out.items()]


# ------------------------------------------------------------ object level
def parse_envelope(blob: bytes, digest: str) -> Dict[str, Any]:
    try:
        env = msgpack.unpackb(blob, raw=False)
    except Exception as e:  # noqa: BLE001 - msgpack raises many types
        raise CorruptObject(
            f"unreadable object envelope for {digest}: {e!r}") from e
    if not isinstance(env, dict) or env.get("v") != OBJECT_VERSION:
        raise CorruptObject(f"bad object envelope/version for {digest}")
    return env


def _apply_delta_blob(digest: str, payload: bytes, base: bytes) -> bytes:
    try:
        return delta_decode(payload, base)
    except (CorruptObject, CodecUnavailable):
        raise
    except Exception as e:  # noqa: BLE001
        raise CorruptObject(
            f"unreadable delta object {digest}: {e!r}") from e


def _object_items(env: Dict[str, Any], digest: str,
                  base_canon: Optional[bytes],
                  verify: bool) -> Tuple[Dict, Items]:
    """Resolve a parsed envelope to (meta, items), with delta bases
    supplied as the base object's canonical payload bytes."""
    fmt = env.get("format")
    if env.get("fp") is not None:
        if fmt == "full":
            meta, items = decode_chunk_items(env["payload"], verify=verify)
        elif fmt == "block_delta":
            if base_canon is None:
                raise CorruptObject(
                    f"delta object {digest} without its base payload")
            # The base canonical came out of a verified read of the base
            # object — its CRCs need no second check here.
            _, base_items = decode_chunk_items(base_canon, verify=False)
            try:
                records = block_delta_decode(env["payload"])
            except (CorruptObject, CodecUnavailable):
                raise
            except Exception as e:  # noqa: BLE001
                raise CorruptObject(
                    f"unreadable block-delta object {digest}: {e!r}") from e
            items = patch_items(base_items, records)
            meta = {}
        else:
            raise CorruptObject(f"unknown object format {fmt!r}")
        if verify:
            # Lossy-coded full objects intentionally decode to different
            # tensors than were fingerprinted; their per-record CRC is
            # the integrity check instead (same rule as the store).
            lossless = env.get("codec") in (None, "none", "zstd")
            verify_fp_items(digest, env["fp"], items,
                            check_content=(fmt != "full" or lossless))
        return meta, items
    if fmt == "full":
        return decode_chunk_items(env["payload"], verify=verify)
    if fmt != "delta":
        raise CorruptObject(f"unknown object format {fmt!r}")
    if base_canon is None:
        raise CorruptObject(
            f"delta object {digest} without its base payload")
    canon = _apply_delta_blob(digest, env["payload"], base_canon)
    if verify and blake2_hex(canon) != digest:
        raise CorruptObject(f"digest mismatch for {digest}")
    return decode_chunk_items(canon, verify=verify)


def decode_object(blob: bytes, digest: str,
                  base_canon: Optional[bytes] = None,
                  verify: bool = True) -> Tuple[Dict, Items]:
    """Envelope blob -> (meta, items): the whole read/decompress/verify
    stage of a restore read, runnable in a worker process."""
    env = parse_envelope(blob, digest)
    return _object_items(env, digest, base_canon, verify)


def canonical_object(blob: bytes, digest: str,
                     base_canon: Optional[bytes] = None,
                     verify: bool = True) -> bytes:
    """Envelope blob -> canonical (codec='none') payload bytes — the
    currency delta decoding needs for its base.  Mirrors
    ``ChunkStore.read_canonical`` for one envelope."""
    env = parse_envelope(blob, digest)
    fmt = env.get("format")
    if env.get("fp") is None and fmt == "full" and env.get("codec") == "none":
        canon = env["payload"]
        if verify and blake2_hex(canon) != digest:
            raise CorruptObject(f"digest mismatch for {digest}")
        return canon
    if env.get("fp") is None and fmt == "delta":
        if base_canon is None:
            raise CorruptObject(
                f"delta object {digest} without its base payload")
        canon = _apply_delta_blob(digest, env["payload"], base_canon)
        if verify and blake2_hex(canon) != digest:
            raise CorruptObject(f"digest mismatch for {digest}")
        return canon
    meta, items = _object_items(env, digest, base_canon, verify)
    canon = encode_chunk_items(items, meta if env.get("fp") is None else {},
                               "none")
    if verify and env.get("fp") is None and blake2_hex(canon) != digest:
        raise CorruptObject(f"digest mismatch for {digest}")
    return canon


# ----------------------------------------------------------------- file IO
def file_read(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def file_write_atomic(path: str, data: bytes, fsync: bool = False,
                      tag: Optional[str] = None) -> int:
    """Atomic tmp+rename(+fsync) write — the worker-side mirror of
    ``backends.localfs.atomic_write``.  ``tag`` carries the *coordinator
    process's* pid-tid pair so tmp files keep the parent's identity and
    ``sweep_tmp``'s own-pid liveness rule still protects in-flight
    writes; the worker pid is appended for uniqueness."""
    if tag is None:
        tag = f"{os.getpid():x}-{threading.get_ident():x}"
    else:
        tag = f"{tag}-{os.getpid():x}"
    tmp = f"{path}.tmp-{tag}"
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(tmp, "wb") as f:
        f.write(data)
        if fsync:
            f.flush()
            os.fsync(f.fileno())
    os.replace(tmp, path)
    if fsync and parent:
        fd = os.open(parent, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
    return len(data)


# ------------------------------------------------------- test/probe helpers
def ping() -> Dict[str, Any]:
    """Worker liveness + hygiene probe (pid for kill tests, jax flag for
    the no-jax-in-workers invariant)."""
    return {"pid": os.getpid(), "jax": "jax" in sys.modules}


def loaded_modules() -> List[str]:
    return sorted(sys.modules)


def echo(value: Any) -> Any:
    if isinstance(value, (bytes, bytearray)):
        return len(value)
    return value


def sleep_for(seconds: float) -> float:
    time.sleep(float(seconds))
    return float(seconds)


def boom(message: str = "boom") -> None:
    raise RuntimeError(message)


# ------------------------------------------------------------ fn registry
WORKER_FNS: Dict[str, Any] = {
    "ping": ping,
    "modules": loaded_modules,
    "echo": echo,
    "sleep": sleep_for,
    "boom": boom,
    "blake2_hex": blake2_hex,
    "zstd_compress": zstd_compress,
    "zstd_decompress": zstd_decompress,
    "fingerprint_pairs": fingerprint_pairs,
    "delta_encode":
        lambda cur, base, compress=None: delta_encode(cur, base,
                                                      compress=compress),
    "delta_decode": delta_decode,
    "block_delta_encode":
        lambda records, compress=None: block_delta_encode(
            records, compress=compress),
    "block_delta_decode": block_delta_decode,
    "encode_chunk_pieces": encode_chunk_pieces,
    "decode_chunk_items": decode_chunk_items,
    "decode_object": decode_object,
    "canonical_object": canonical_object,
    "file_read": file_read,
    "file_write_atomic": file_write_atomic,
}


def run(fn_id: str, *args) -> Any:
    """Inline (same-process) execution of a worker fn — the thread
    backend's degenerate dispatch."""
    return WORKER_FNS[fn_id](*args)


# ------------------------------------------------------- worker main loop
def _read_shm(name: str, length: int) -> bytes:
    """Fetch a parent-owned shared-memory payload WITHOUT registering it
    with this process's multiprocessing resource tracker (attaching via
    SharedMemory would, and a tracker that learned the name unlinks it
    when this worker dies — destroying a segment the parent still owns).
    On Linux the segment is simply a file under /dev/shm."""
    try:
        with open(os.path.join(SHM_DIR, name), "rb") as f:
            return f.read(length)
    except FileNotFoundError:  # pragma: no cover - non-Linux fallback
        from multiprocessing import resource_tracker, shared_memory
        shm = shared_memory.SharedMemory(name=name)
        try:
            resource_tracker.unregister(shm._name, "shared_memory")
        except Exception:  # noqa: BLE001 - best effort on odd platforms
            pass
        try:
            return bytes(shm.buf[:length])
        finally:
            shm.close()


def _resolve_shm(obj: Any) -> Any:
    if isinstance(obj, tuple):
        if len(obj) == 3 and obj[0] == SHM_MARK:
            return _read_shm(obj[1], obj[2])
        return tuple(_resolve_shm(v) for v in obj)
    if isinstance(obj, list):
        return [_resolve_shm(v) for v in obj]
    if isinstance(obj, dict):
        return {k: _resolve_shm(v) for k, v in obj.items()}
    return obj


# This worker's response scratch files (one per pool that talks to us —
# in practice one), kept open so tmpfs pages are allocated once and
# reused across responses instead of create/write/unlink churn per call.
_SCRATCH: Dict[str, Any] = {}


def _scratch_file(name: str):
    f = _SCRATCH.get(name)
    if f is None:
        f = open(os.path.join(SHM_DIR, name), "wb+")
        _SCRATCH[name] = f
    return f


def _stage_result(obj: Any, fobj: Any, min_bytes: int,
                  offset: List[int]) -> Any:
    """Replace payload-sized bytes inside a result with scratch-file
    offset markers ``(SHM_MARK, offset:int, length)`` — the
    response-side mirror of ``_resolve_shm`` (whose argument markers
    carry a segment *name*; an int in slot 1 disambiguates)."""
    if isinstance(obj, (bytes, bytearray)) and len(obj) >= min_bytes:
        off = offset[0]
        fobj.seek(off)
        fobj.write(obj)
        offset[0] = off + len(obj)
        return (SHM_MARK, off, len(obj))
    if isinstance(obj, tuple):
        return tuple(_stage_result(v, fobj, min_bytes, offset)
                     for v in obj)
    if isinstance(obj, list):
        return [_stage_result(v, fobj, min_bytes, offset) for v in obj]
    if isinstance(obj, dict):
        return {k: _stage_result(v, fobj, min_bytes, offset)
                for k, v in obj.items()}
    return obj


def _error_kind(exc: BaseException) -> str:
    if isinstance(exc, CorruptObject):
        return "corrupt"
    if isinstance(exc, CodecUnavailable):
        return "codec"
    if isinstance(exc, FileNotFoundError):
        return "missing"
    return "error"


def worker_main(rd=None, wr=None) -> int:
    """Stdio task loop of one subprocess worker: pickled (fn_id, args)
    in, pickled ("ok", result) | ("err", kind, message, traceback) out.
    ``None`` (or EOF) shuts down.  Exceptions cross the pipe as plain
    strings — never pickled objects — because this module lives under a
    different name in the parent (see module docstring)."""
    rd = rd if rd is not None else sys.stdin.buffer
    wr = wr if wr is not None else sys.stdout.buffer
    # stdout IS the protocol channel: reroute stray prints to stderr.
    sys.stdout = sys.stderr
    while True:
        try:
            msg = pickle.load(rd)
        except EOFError:
            return 0
        if msg is None:
            return 0
        fn_id, args = msg[0], msg[1]
        resp_spec = msg[2] if len(msg) > 2 else None
        try:
            fn = WORKER_FNS[fn_id]
            result = fn(*_resolve_shm(args))
            if resp_spec is not None and os.path.isdir(SHM_DIR):
                fobj = _scratch_file(resp_spec[0])
                result = _stage_result(result, fobj,
                                       int(resp_spec[1]), [0])
                fobj.flush()
            resp = ("ok", result)
        except BaseException as e:  # noqa: BLE001 - marshal everything back
            resp = ("err", _error_kind(e), f"{type(e).__name__}: {e}",
                    traceback.format_exc())
        try:
            out = pickle.dumps(resp, protocol=pickle.HIGHEST_PROTOCOL)
        except Exception as e:  # noqa: BLE001 - unpicklable result
            out = pickle.dumps(
                ("err", "error", f"unpicklable worker result: {e!r}", ""),
                protocol=pickle.HIGHEST_PROTOCOL)
        try:
            wr.write(out)
            wr.flush()
        except (BrokenPipeError, OSError):
            return 1


if __name__ == "__main__":  # pragma: no cover - manual debugging entry
    raise SystemExit(worker_main())

"""Content-addressed chunk store with cross-step dedup and delta encoding.

The store is an *addressing and codec core* layered over a swappable
:class:`~repro.checkpoint.backends.base.StorageBackend` that owns all
object-byte IO (see docs/storage.md).  The default ``local`` backend
keeps the classic on-disk layout:

    root/
      objects/ab/abcdef...123.chunk   # one file per distinct content digest
      manifests/manifest-00000100.json
      LATEST                          # atomic pointer to the newest manifest

while ``memory`` holds objects in RAM and ``tiered`` composes a hot RAM
tier over the durable ``objects/`` tree with asynchronous spill,
promotion-on-read, and LRU eviction.  Everything below the digest — the
envelope formats, dedup, delta decisions, refcounts — is
backend-independent; everything below the byte-blob — atomic writes, tmp
sweeps, tier placement — lives in ``repro.checkpoint.backends``.

Every chunk is keyed by the blake2b digest of its *canonical* payload (the
codec="none" serialization of the unit's tensors, metadata excluded, so the
same tensors always hash the same regardless of save step or codec).  A
re-saved-but-unchanged unit therefore costs a host snapshot and a hash — no
write, no extra disk (GoCkpt/DataStates-style inter-step dedup composed
with the paper's layer selectivity).

An object file is a small msgpack envelope holding one of:

- ``full``: the chunk blob encoded with the store codec, or
- ``delta``: a sparse XOR diff (``compression.delta_encode``) of this
  chunk's canonical payload against the canonical payload of a *full* base
  object, recorded by digest.  Deltas always point at a full object, so
  reconstruction is exactly one base read + one patch; the store rebases
  (writes a full object again) when the diff stops being materially
  smaller than a full write OR after ``rebase_every`` consecutive deltas,
  bounding how many checkpoints one base object can underpin.
- ``block_delta``: the fingerprint pipeline's v2 format — only the blocks
  the device-side fingerprint compare flagged dirty, patched onto a full
  base on read.  Written via ``write_fp`` without the store (or saver)
  ever materializing the full canonical payload.

Objects written by ``write`` are addressed by the blake2b of their
canonical payload; objects written by ``write_fp`` are addressed by the
blake2b of their **fingerprint table** (the envelope carries the table
under ``"fp"``, which is also how readers tell the schemes apart and how
verification works: reads of fp-addressed objects recompute the table from
the reconstructed tensors with the numpy oracle).  The two schemes share
one digest namespace and one refcount/GC/manifest machinery; they simply
never dedup against each other.

Lifetimes are refcounted: each committed manifest holds one reference per
entry digest (plus one per delta base), and ``gc_objects`` deletes objects
whose count has dropped to zero — replacing the old step-directory
retention deletes.  Refcounts are derived in memory from the committed
manifests (see ``CheckpointManager``), so a crash can never corrupt them;
orphans from an interrupted save are swept by the next GC.

Chunk writes are atomic (tmp + rename + fsync) so a crash mid-save never
corrupts a previous checkpoint — the manifest is committed last and only
references fully-written objects.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import threading
from collections import Counter
from pathlib import Path
from typing import (TYPE_CHECKING, Any, Dict, Iterable, Iterator, Optional,
                    Tuple)

import msgpack

from repro.checkpoint import compression, faults, serial, tracing
from repro.checkpoint import fingerprint as fputil
from repro.checkpoint.async_io import INLINE_DISPATCH, IoDispatch
from repro.checkpoint.backends import StorageBackend, make_backend
from repro.checkpoint.backends.retry import RetryPolicy
# Back-compat alias: the manifest store and several tests import the
# atomic-write protocol from here; the implementation now lives with the
# rest of the filesystem IO in the backends package.
from repro.checkpoint.backends.localfs import atomic_write as _atomic_write  # noqa: F401,E501

if TYPE_CHECKING:
    from repro.checkpoint.block_cache import BlockCache

PyTree = Any

OBJECT_VERSION = 1
DIGEST_BYTES = 20  # blake2b-160: plenty for collision-resistance here
# A delta must beat a full write by at least this factor to be stored; the
# margin auto-rebases drifted units (their diffs grow until a full wins).
DELTA_RATIO = 0.9
# Force a full rebase after this many consecutive deltas of one unit even
# when each diff is tiny: every delta of a slowly-drifting unit pins the
# SAME full base, so an unbounded run would make that one object file a
# single point of failure for the unit across the whole retention window.
REBASE_EVERY = 4
# Reconstructed canonical payloads cached for delta encoding (save path
# diffs against the previous full object without re-reading it every event).
CANON_CACHE_BYTES = 64 << 20
# Transient-IO retry schedule for object reads: a flaky backend gets a
# few quick retries BEFORE the store declares corruption and restore
# spends a fallback (see docs/resiliency.md).
READ_RETRY = RetryPolicy(attempts=3, base_delay=0.002, max_delay=0.05)


def content_digest(blob: bytes) -> str:
    return hashlib.blake2b(blob, digest_size=DIGEST_BYTES).hexdigest()


class ReadSession:
    """Scoped read-once cache over one logical restore pass.

    The restore plan routinely wants the same object more than once: two
    units whose content dedup'd to one digest, several block-deltas
    patching against one shared full base, or a digest needed both as a
    decoded tree (it is a unit's entry) and as canonical bytes (it anchors
    a v1 XOR delta).  A session memoizes the three representations —
    envelope, canonical payload, decoded tree — per digest, with per-key
    in-flight coalescing so concurrent executor threads asking for the
    same object block on one read instead of racing duplicate I/O.

    Failures are memoized too: a corrupt object shared by several units
    fails all of them from a single read attempt (the fallback chain takes
    over per unit).  ``release`` drops every representation of a digest
    once the planner says no remaining target needs it, bounding the
    session's memory to the live working set rather than the checkpoint.

    ``stats`` counts actual object I/O: ``object_reads`` distinct envelope
    reads and ``bytes_read`` object-file bytes — the numbers the restore
    engine reports and the dedup tests pin down.
    """

    def __init__(self, store: "ChunkStore", *, verify: bool = True):
        self.store = store
        self.verify = verify
        self._lock = threading.Lock()
        # (repr, digest) -> {"event": Event, "value":..., "error":...}
        self._cells: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self.stats = {"object_reads": 0, "bytes_read": 0}
        # digest -> tier it was served from ("hot"/"durable"/"local"/...):
        # the restore engine's tier provenance dimension.
        self.tiers: Dict[str, str] = {}
        self.tier_reads: Dict[str, int] = {}

    def _memoized(self, table: str, digest: str, fn):
        key = (table, digest)
        while True:
            with self._lock:
                cell = self._cells.get(key)
                if cell is None:
                    cell = {"event": threading.Event(), "value": None,
                            "error": None, "owner": threading.get_ident()}
                    self._cells[key] = cell
                    owner = True
                else:
                    owner = False
            if not owner:
                if cell["owner"] == threading.get_ident() \
                        and not cell["event"].is_set():
                    # Re-entrant request: a (corrupt) delta envelope whose
                    # base chain loops back on itself.  Waiting would
                    # deadlock on our own in-flight cell — surface it as
                    # corruption so the fallback chain takes over.
                    raise serial.ChunkCorruption(
                        f"object dependency cycle at {digest}")
                cell["event"].wait()
                with self._lock:
                    # release() may have dropped the cell between the wait
                    # and this lookup — recompute in that (rare) case.
                    if self._cells.get(key) is not cell:
                        continue
                if cell["error"] is not None:
                    raise cell["error"]
                return cell["value"]
            try:
                cell["value"] = fn()
            except BaseException as e:  # noqa: BLE001 - memoize failures too
                cell["error"] = e
                raise
            finally:
                cell["event"].set()
            return cell["value"]

    def envelope(self, digest: str) -> Dict[str, Any]:
        def read():
            # Locate before the read: a tiered backend promotes on read,
            # so asking afterwards would always answer "hot".
            tier = self.store.locate(digest)
            env = self.store._read_envelope(digest)
            nbytes = self.store.object_info(digest)["nbytes"]
            with self._lock:
                self.stats["object_reads"] += 1
                self.stats["bytes_read"] += int(nbytes)
                if tier is not None:
                    self.tiers[digest] = tier
                    self.tier_reads[tier] = self.tier_reads.get(tier, 0) + 1
            return env

        return self._memoized("env", digest, read)

    def canonical(self, digest: str) -> bytes:
        return self._memoized(
            "canon", digest,
            lambda: self.store.read_canonical(digest, verify=self.verify,
                                              session=self))

    def read(self, digest: str) -> Tuple[PyTree, Dict]:
        return self._memoized(
            "tree", digest,
            lambda: self.store.read_digest(digest, verify=self.verify,
                                           session=self))

    # ---- process-backend read path ----
    # The offload variants keep the whole read/decompress/verify stage in
    # a subprocess worker: the parent fetches the raw envelope blob (tier
    # provenance, retries, and fault injection all live backend-side and
    # must stay in-process), ships it plus any delta base's canonical
    # bytes through the dispatch, and gets back flat items to unflatten.
    # Delta bases come from the *manifest* (ChunkRef.delta_base) rather
    # than from parsing the envelope parent-side — bases are full objects
    # by store invariant, so the chain is exactly one level deep.  The
    # memo tables are shared with the inline path ("canon"/"tree"), so
    # release() and mixed usage behave identically.

    def object_blob(self, digest: str) -> bytes:
        """Raw envelope blob with the same read accounting as
        ``envelope()`` (distinct memo table; the two paths never both run
        for one digest in one session)."""
        def read():
            tier = self.store.locate(digest)
            blob = self.store._backend_read(digest)
            with self._lock:
                self.stats["object_reads"] += 1
                self.stats["bytes_read"] += len(blob)
                if tier is not None:
                    self.tiers[digest] = tier
                    self.tier_reads[tier] = self.tier_reads.get(tier, 0) + 1
            return blob

        return self._memoized("blob", digest, read)

    def canonical_offload(self, digest: str,
                          base_digest: Optional[str] = None) -> bytes:
        dispatch = self.store.dispatch

        def build():
            base = (self.canonical_offload(base_digest)
                    if base_digest else None)
            blob = self.object_blob(digest)
            return dispatch.call("canonical_object", blob, digest, base,
                                 self.verify, lane="restore")

        return self._memoized("canon", digest, build)

    def read_offload(self, digest: str,
                     base_digest: Optional[str] = None
                     ) -> Tuple[PyTree, Dict]:
        dispatch = self.store.dispatch

        def build():
            base = (self.canonical_offload(base_digest)
                    if base_digest else None)
            blob = self.object_blob(digest)
            meta, items = dispatch.call("decode_object", blob, digest,
                                        base, self.verify, lane="restore")
            return serial.items_to_tree(items), meta

        return self._memoized("tree", digest, build)

    def release(self, digest: str) -> None:
        """Drop every cached representation of ``digest`` (its last
        dependent has consumed it)."""
        with self._lock:
            for table in ("env", "blob", "canon", "tree"):
                self._cells.pop((table, digest), None)


def _ref_stored(fmt: str) -> str:
    """Envelope format -> ChunkRef.stored: manifests only distinguish
    full vs delta (for refcounting bases and delta-run replay); the
    concrete delta encoding (XOR v1 vs block-sparse v2) lives in the
    envelope."""
    return "full" if fmt == "full" else "delta"


@dataclasses.dataclass(frozen=True)
class ChunkRef:
    step: int
    unit: str
    kind: str           # "weights" | "opt"
    relpath: str
    nbytes: int         # size of the object file on disk
    digest: str = ""    # blake2b of the canonical payload (required to read)
    stored: str = "full"            # "full" | "delta" (on-disk encoding)
    delta_base: Optional[str] = None  # digest of the full base, if delta
    # Shard objects only: the ShardSpec JSON recording which index blocks
    # of the unit's global arrays this object covers (participant id +
    # per-leaf shape/dtype/blocks — see repro.checkpoint.sharded).  None
    # for classic global-array objects.  The spec lives in the manifest,
    # not the envelope: the same content digest may be referenced with
    # different specs by different save topologies.
    spec: Optional[Dict[str, Any]] = None

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        if d.get("spec") is None:
            d.pop("spec", None)  # keep global-object manifests unchanged
        return d

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "ChunkRef":
        return ChunkRef(**d)


class ChunkStore:
    def __init__(self, root: Path | str, *, codec: str = "auto",
                 fsync: bool = False, delta: bool = True,
                 delta_ratio: float = DELTA_RATIO,
                 rebase_every: int = REBASE_EVERY,
                 backend: "str | StorageBackend" = "local",
                 spill_threads: int = 2,
                 hot_budget_bytes: Optional[int] = None,
                 read_retry: Optional[RetryPolicy] = None,
                 remote_opts: Optional[Dict[str, Any]] = None,
                 dispatch: Optional[IoDispatch] = None,
                 block_cache: Optional["BlockCache"] = None):
        self.root = Path(root)
        self.codec = compression.resolve_codec(codec)
        self.fsync = fsync
        # Worker dispatch for the hot byte transforms (encode, delta,
        # hashing of envelopes happens backend-side).  Inline by default;
        # a process-backed TransferPool's dispatch ships them to
        # subprocess workers.  Pre-composed backends (the manager's
        # tiered compositions) carry their own dispatch already.
        self.dispatch = dispatch if dispatch is not None else INLINE_DISPATCH
        self.backend = make_backend(backend, self.root, fsync=fsync,
                                    spill_threads=spill_threads,
                                    hot_budget_bytes=hot_budget_bytes,
                                    remote_opts=remote_opts,
                                    dispatch=self.dispatch)
        self.read_retry = read_retry if read_retry is not None \
            else READ_RETRY
        # Process-lifetime digest->blob cache underneath every backend
        # read (serving fleets: K variants/hot-swaps share one copy of
        # each dedup object — see checkpoint/block_cache.py).  The cache
        # may be shared across stores; the store never closes it.
        self.block_cache = block_cache
        # Monotonic count of reads that actually reached the backend
        # (cache hits excluded) — the bench gate's "object reads" axis.
        self.backend_reads = 0
        self.delta = delta
        self.delta_ratio = delta_ratio
        self.rebase_every = max(1, rebase_every)
        self._lock = threading.Lock()
        self._refcounts: Counter = Counter()
        # digest -> {"stored", "base", "nbytes"} for objects we've touched
        self._info: Dict[str, Dict[str, Any]] = {}
        # (unit, kind) -> consecutive deltas written since the last full
        self._delta_runs: Dict[Tuple[str, str], int] = {}
        # digest -> unpacked fingerprint table for fp-addressed objects
        # (populated on write_fp; lazily loaded from envelopes after restart)
        self._fp_tables: Dict[str, list] = {}
        # digest -> Event for writes in flight: concurrent writer threads
        # persisting bitwise-identical units dedup instead of racing
        self._inflight: Dict[str, threading.Event] = {}
        self._canon_cache: Dict[str, bytes] = {}
        self._canon_cache_bytes = 0
        # Monotonic (never reset per event): transient backend-read
        # errors that a bounded retry absorbed.  The restore engine
        # delta-samples it into last_stats["io_retries"] — distinct from
        # fallbacks, which burn a restore candidate.
        self.io_retries = 0
        # Digests the scrubber declared unrecoverable (corrupt in every
        # tier): restore's planner skips them up front so fallback chains
        # never discover the corruption mid-restore.  Persisted in
        # QUARANTINE.json next to the manifests; cleared per digest when
        # a later scrub finds (or rebuilds) a good copy.
        self._quarantine: Dict[str, Dict[str, Any]] = \
            self._load_quarantine()
        self.stats: Dict[str, int] = {}
        self.reset_stats()

    # ---- addressing (backend-independent) ----
    def object_path(self, digest: str) -> Path:
        """Filesystem path of ``digest`` when a path-backed tier exists
        (tests and offline tools poke object files directly)."""
        p = self.backend.path_of(digest)
        if p is None:
            raise NotImplementedError(
                f"backend {self.backend.name!r} has no filesystem paths")
        return p

    def object_relpath(self, digest: str) -> str:
        """Advisory root-relative location recorded in manifests.  Pure
        string math — the digest, not the path, is what reads resolve."""
        return f"objects/{digest[:2]}/{digest}.chunk"

    def has(self, digest: str) -> bool:
        return self.backend.has(digest)

    def exists(self, ref: ChunkRef) -> bool:
        return bool(ref.digest) and self.backend.has(ref.digest)

    def iter_digests(self) -> Iterator[str]:
        return self.backend.keys()

    def locate(self, digest: str) -> Optional[str]:
        """Fastest tier currently holding ``digest`` (backend-specific
        name, e.g. "hot"/"durable"/"local"; None if absent)."""
        return self.backend.locate(digest)

    # ---- stats ----
    def reset_stats(self) -> None:
        with self._lock:
            self.stats = {"written_bytes": 0, "logical_bytes": 0,
                          "dedup_hits": 0, "delta_chunks": 0,
                          "full_chunks": 0, "hashed_bytes": 0}

    def _bump(self, **kw: int) -> None:
        with self._lock:
            for k, v in kw.items():
                self.stats[k] += v

    # ---- canonical-payload LRU cache (delta encoding hot path) ----
    def _canon_cached(self, digest: str) -> Optional[bytes]:
        with self._lock:
            canon = self._canon_cache.pop(digest, None)
            if canon is not None:
                self._canon_cache[digest] = canon  # move to MRU position
            return canon

    def _canon_remember(self, digest: str, canon: bytes) -> None:
        if len(canon) > CANON_CACHE_BYTES:
            return
        with self._lock:
            if digest in self._canon_cache:
                return
            # evict least-recently-used (dicts iterate in insertion order;
            # _canon_cached reinserts on hit, so the head is the LRU entry)
            while (self._canon_cache_bytes + len(canon) > CANON_CACHE_BYTES
                   and self._canon_cache):
                lru = next(iter(self._canon_cache))
                self._canon_cache_bytes -= len(self._canon_cache.pop(lru))
            self._canon_cache[digest] = canon
            self._canon_cache_bytes += len(canon)

    # ---- quarantine (scrub-demoted digests; see checkpoint/scrub.py) ----
    @property
    def quarantine_path(self) -> Path:
        return self.root / "QUARANTINE.json"

    def _load_quarantine(self) -> Dict[str, Dict[str, Any]]:
        try:
            return dict(json.loads(self.quarantine_path.read_bytes()))
        except FileNotFoundError:
            return {}
        except Exception:  # noqa: BLE001 - a mangled sidecar must not
            return {}      # take the store down; scrub rewrites it

    def quarantined(self, digest: str) -> bool:
        with self._lock:
            return digest in self._quarantine

    def quarantine(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {d: dict(v) for d, v in self._quarantine.items()}

    def set_quarantine(self, entries: Dict[str, Dict[str, Any]]) -> None:
        """Replace the quarantine set (scrubber-only), persisted
        atomically so a crash never leaves a torn sidecar."""
        with self._lock:
            self._quarantine = {d: dict(v) for d, v in entries.items()}
        if entries:
            _atomic_write(self.quarantine_path,
                          json.dumps(entries, indent=2).encode(),
                          fsync=self.fsync)
        else:
            try:
                self.quarantine_path.unlink()
            except FileNotFoundError:
                pass

    # ---- object io ----
    def _backend_read(self, digest: str) -> bytes:
        """Object blob by digest: the block cache when one is attached
        (content addressing makes cached blobs immutable-safe), the
        retried backend read otherwise."""
        if self.block_cache is not None:
            return self.block_cache.get(
                digest, lambda: self._backend_read_direct(digest))
        return self._backend_read_direct(digest)

    def _backend_read_direct(self, digest: str) -> bytes:
        """Backend read with bounded transient-IO retries.

        A flaky-but-alive backend (remote blip, injected error rate)
        raises OSErrors that are NOT corruption; retrying a few times
        here keeps restore from burning an older-manifest fallback on a
        transient.  FileNotFoundError passes straight through (absence
        is an answer); a transient that survives every retry is then
        declared corruption so the fallback machinery takes over."""
        def on_retry(attempt: int, exc: BaseException) -> None:
            with self._lock:
                self.io_retries += 1

        with self._lock:
            self.backend_reads += 1
        try:
            return self.read_retry.run(
                lambda: self.backend.read(digest), key=digest,
                on_retry=on_retry)
        except FileNotFoundError:
            raise
        except OSError as e:
            raise serial.ChunkCorruption(
                f"object {digest} unreadable after "
                f"{self.read_retry.attempts} attempts: {e!r}") from e

    def _parse_envelope(self, digest: str, blob: bytes, *,
                        remember: bool = True) -> Dict[str, Any]:
        """Unpack + sanity-check an envelope blob.  ``remember=False``
        keeps a scrub probe of a possibly-corrupt copy from poisoning
        the info cache."""
        # Any parse failure of a corrupt envelope must surface as
        # ChunkCorruption so the restore fallback path catches it.
        try:
            env = msgpack.unpackb(blob, raw=False)
        except Exception as e:  # noqa: BLE001 - msgpack raises many types
            raise serial.ChunkCorruption(
                f"unreadable object envelope for {digest}: {e!r}") from e
        if not isinstance(env, dict) or env.get("v") != OBJECT_VERSION:
            raise serial.ChunkCorruption(
                f"bad object envelope/version for {digest}")
        if remember:
            with self._lock:
                self._info[digest] = {"stored": env.get("format"),
                                      "base": env.get("base"),
                                      "codec": env.get("codec"),
                                      "nbytes": len(blob)}
        return env

    def _read_envelope(self, digest: str) -> Dict[str, Any]:
        return self._parse_envelope(digest, self._backend_read(digest))

    def object_info(self, digest: str) -> Dict[str, Any]:
        """{"stored": "full"|"delta", "base": digest|None, "nbytes": int}."""
        with self._lock:
            info = self._info.get(digest)
        if info is None:
            self._read_envelope(digest)
            with self._lock:
                info = self._info[digest]
        return dict(info)

    def _write_object(self, digest: str, env: Dict[str, Any]) -> int:
        with tracing.span("ckpt.write.store"):
            blob = msgpack.packb(env, use_bin_type=True)
            faults.crash_point("object_write")
            self.backend.write(digest, blob)
        with self._lock:
            self._info[digest] = {"stored": env["format"],
                                  "base": env.get("base"),
                                  "codec": env.get("codec"),
                                  "nbytes": len(blob)}
        return len(blob)

    # ---- blob-level copy (merge engine: backend-to-backend transfer) ----
    def read_object_bytes(self, digest: str) -> bytes:
        """The raw envelope blob of ``digest`` — no decode, no verify.
        The merge engine moves objects between stores (and backends:
        RAM-tier source to durable output) with this + write_object_bytes
        without ever materializing tensors."""
        return self._backend_read(digest)

    def write_object_bytes(self, digest: str, blob: bytes) -> int:
        """Store a pre-encoded envelope blob under its digest (atomic,
        idempotent — content addressing guarantees equal payloads)."""
        return self.backend.write(digest, blob)

    def read_canonical(self, digest: str, *, verify: bool = True,
                       session: Optional[ReadSession] = None) -> bytes:
        """The codec='none' chunk blob for ``digest``, resolving deltas.

        fp-addressed objects reconstruct their tree first (their digest is
        over the fingerprint table, not the canonical payload — the table
        recompute inside ``_tree_from_fp_env`` is their integrity check).
        A ``session`` routes the envelope and base reads through its
        read-once cache (restore engine hot path)."""
        cached = self._canon_cached(digest)
        if cached is not None:
            return cached
        env = (session.envelope(digest) if session is not None
               else self._read_envelope(digest))
        canon = self._canonical_from_env(digest, env, verify=verify,
                                         session=session)
        self._canon_remember(digest, canon)
        return canon

    def _canonical_from_env(self, digest: str, env: Dict[str, Any], *,
                            verify: bool,
                            session: Optional[ReadSession] = None) -> bytes:
        """Resolve an already-parsed envelope to its canonical blob (the
        decode half of ``read_canonical``, shared with the scrubber's
        per-tier blob verification)."""
        if env.get("fp") is not None:
            tree, _ = self._tree_from_fp_env(digest, env, verify=verify,
                                             session=session)
            canon = serial.encode_chunk(tree, meta={}, codec="none")
        elif env.get("format") == "full":
            if env["codec"] == "none":
                canon = env["payload"]
            else:
                # transcode: decode the stored blob, re-encode canonically
                tree, meta = serial.decode_chunk(env["payload"], verify=verify)
                canon = serial.encode_chunk(tree, meta=meta, codec="none")
        elif env.get("format") == "delta":
            base = (session.canonical(env["base"]) if session is not None
                    else self.read_canonical(env["base"], verify=verify))
            canon = self._apply_delta(digest, env, base)
        else:
            raise serial.ChunkCorruption(
                f"unknown object format {env.get('format')!r}")
        if (verify and env.get("fp") is None
                and content_digest(canon) != digest):
            raise serial.ChunkCorruption(f"digest mismatch for {digest}")
        return canon

    def verify_object_blob(self, digest: str, blob: bytes) -> Dict[str, Any]:
        """Full integrity check of one envelope blob AGAINST its digest:
        parse, resolve to canonical (following delta bases through the
        store), and compare content/fingerprint digests.  Raises
        ChunkCorruption on any mismatch; returns the parsed envelope on
        success.  ``remember=False`` throughout — probing a suspect
        tier's copy must not poison caches with bad data."""
        env = self._parse_envelope(digest, blob, remember=False)
        self._canonical_from_env(digest, env, verify=True, session=None)
        return env

    def _tree_from_fp_env(self, digest: str, env: Dict[str, Any],
                          *, verify: bool,
                          session: Optional[ReadSession] = None
                          ) -> Tuple[PyTree, Dict]:
        """Reconstruct (tree, meta) of an fp-addressed object and verify it
        by recomputing the fingerprint table with the host oracle."""
        fmt = env.get("format")
        if fmt == "full":
            tree, meta = serial.decode_chunk(env["payload"], verify=verify)
        elif fmt == "block_delta":
            if session is not None:
                base_tree, _ = session.read(env["base"])
            else:
                base_tree, _ = self.read_digest(env["base"], verify=verify)
            try:
                records = compression.block_delta_decode(env["payload"])
                tree = fputil.patch_tree(base_tree, records)
            except (serial.ChunkCorruption, compression.CodecUnavailable):
                raise
            except Exception as e:  # noqa: BLE001
                raise serial.ChunkCorruption(
                    f"unreadable block-delta object {digest}: {e!r}") from e
            meta = {}
        else:
            raise serial.ChunkCorruption(
                f"unknown object format {fmt!r}")
        if verify:
            try:
                tbl = fputil.unpack_table(env["fp"])
            except ValueError as e:
                raise serial.ChunkCorruption(
                    f"bad fingerprint table for {digest}: {e!r}") from e
            if fputil.fp_digest(env["fp"]) != digest:
                raise serial.ChunkCorruption(
                    f"fingerprint digest mismatch for {digest}")
            # Lossy-coded full objects intentionally decode to different
            # tensors than were fingerprinted (the table describes the
            # pre-quantization content, which is what dedup must compare
            # against) — the per-tensor crc in decode_chunk is their
            # integrity check instead.
            if fmt != "full" or env.get("codec") in ("none", "zstd"):
                bb = (tbl[0].block_bytes if tbl
                      else fputil.DEFAULT_BLOCK_BYTES)
                if (fputil.pack_table(fputil.table_of_tree(tree, bb))
                        != env["fp"]):
                    raise serial.ChunkCorruption(
                        f"fingerprint mismatch for reconstructed {digest}")
            with self._lock:
                self._fp_tables[digest] = tbl
        return tree, meta

    def _apply_delta(self, digest: str, env: Dict[str, Any],
                     base: bytes) -> bytes:
        """delta_decode with corruption surfaced as ChunkCorruption (a
        mangled delta record can raise ValueError/zstd/numpy errors — the
        restore fallback must be able to catch them)."""
        try:
            return compression.delta_decode(env["payload"], base)
        except (serial.ChunkCorruption, compression.CodecUnavailable):
            # CodecUnavailable is an environment problem with an actionable
            # message (install zstandard), not data corruption — masking it
            # as ChunkCorruption would send restore on a futile fallback
            # crawl ending in a misleading RestoreError.
            raise
        except Exception as e:  # noqa: BLE001
            raise serial.ChunkCorruption(
                f"unreadable delta object {digest}: {e!r}") from e

    def read_digest(self, digest: str, *, verify: bool = True,
                    session: Optional[ReadSession] = None
                    ) -> Tuple[PyTree, Dict]:
        env = (session.envelope(digest) if session is not None
               else self._read_envelope(digest))
        if env.get("fp") is not None:
            return self._tree_from_fp_env(digest, env, verify=verify,
                                          session=session)
        if env.get("format") == "full":
            return serial.decode_chunk(env["payload"], verify=verify)
        if env.get("format") != "delta":
            raise serial.ChunkCorruption(
                f"unknown object format {env.get('format')!r}")
        base = (session.canonical(env["base"]) if session is not None
                else self.read_canonical(env["base"], verify=verify))
        canon = self._apply_delta(digest, env, base)
        if verify and content_digest(canon) != digest:
            raise serial.ChunkCorruption(f"digest mismatch for {digest}")
        return serial.decode_chunk(canon, verify=verify)

    def read(self, ref: ChunkRef, *, verify: bool = True,
             session: Optional[ReadSession] = None) -> Tuple[PyTree, Dict]:
        if not ref.digest:
            raise serial.ChunkCorruption(
                f"manifest entry for {ref.unit}/{ref.kind} has no content "
                "digest (pre-content-addressing checkpoint); re-save it")
        return self.read_digest(ref.digest, verify=verify, session=session)

    def write(self, step: int, unit: str, kind: str, tree: PyTree,
              *, codec: Optional[str] = None,
              delta_base: Optional[str] = None,
              prev_ref: Optional[ChunkRef] = None) -> ChunkRef:
        """Persist a unit's tensors; dedup by content, delta when smaller.

        ``delta_base`` is the digest of this unit's previous chunk (any
        encoding — the store redirects to its full base).  Pass None to
        force a full object.  ``prev_ref`` is the unit's previous manifest
        entry: it supplies ``delta_base`` implicitly and lets the common
        unchanged-content dedup hit skip the object-envelope disk read
        (important on the first event after a process restart, when the
        in-memory info cache is cold).
        """
        if prev_ref is not None and delta_base is None:
            delta_base = prev_ref.digest or None
        codec = compression.resolve_codec(codec or self.codec)
        canon = serial.encode_chunk(tree, meta={}, codec="none")
        digest = content_digest(canon)
        self._bump(logical_bytes=len(canon), hashed_bytes=len(canon))

        claim = self._claim(digest)
        if claim is None:
            # Dedup hit: the exact content is already stored (this event
            # or a previous one) — cost was a hash, not a write.
            self._canon_remember(digest, canon)  # likely a future base
            return self._dedup_ref(step, unit, kind, digest,
                                   prev_ref=prev_ref)
        try:
            return self._write_new(step, unit, kind, tree, canon, digest,
                                   codec, delta_base)
        finally:
            with self._lock:
                self._inflight.pop(digest, None)
            claim.set()

    def _claim(self, digest: str) -> Optional[threading.Event]:
        """Claim the right to write ``digest``, or return None when the
        object already exists (dedup).  Concurrent writers persisting the
        same content wait for the in-flight claim instead of racing.

        The existence check happens under the same lock as the claim
        insert: a thread descheduled between a stale negative ``has``
        and taking the lock must not claim (and double-write/double-
        count) an object whose writer finished in between.  The winner
        always completes its backend write before releasing the claim,
        so a fresh ``has`` under the lock is authoritative."""
        while True:
            with self._lock:
                other = self._inflight.get(digest)
                if other is None:
                    if self.backend.has(digest):
                        return None
                    claim = self._inflight[digest] = threading.Event()
                    return claim
            other.wait()  # then loop: has(digest) is now true (or retry)

    def _dedup_ref(self, step: int, unit: str, kind: str, digest: str,
                   *, prev_ref: Optional[ChunkRef] = None) -> ChunkRef:
        """ChunkRef for a dedup hit.  ``prev_ref`` (the unit's previous
        manifest entry) supplies stored/base/nbytes without the
        object-envelope disk read the cold-cache path needs."""
        if prev_ref is not None and prev_ref.digest == digest:
            info = {"stored": prev_ref.stored, "base": prev_ref.delta_base,
                    "nbytes": prev_ref.nbytes}
            with self._lock:
                self._info.setdefault(digest, dict(info))
        else:
            # Rare path (cross-unit dedup or content reverting to an older
            # digest) with a cold info cache: reads the object envelope
            # once to learn stored/base/nbytes — the manifest needs them to
            # pin delta bases — then stays cached for subsequent hits.
            info = self.object_info(digest)
        self._bump(dedup_hits=1)
        return ChunkRef(step=step, unit=unit, kind=kind,
                        relpath=self.object_relpath(digest),
                        nbytes=info["nbytes"], digest=digest,
                        stored=_ref_stored(info["stored"]),
                        delta_base=info["base"])

    def _write_new(self, step: int, unit: str, kind: str, tree: PyTree,
                   canon: bytes, digest: str, codec: str,
                   delta_base: Optional[str]) -> ChunkRef:
        # Compression runs through the dispatch: inline under the thread
        # backend (same workers.py code), in a subprocess worker under the
        # process backend — identical bytes either way.
        with tracing.span("ckpt.write.encode"):
            full_payload = canon if codec == "none" else \
                self.dispatch.call("encode_chunk_items",
                                   serial.tree_to_items(tree), {}, codec)

        # Try a delta against the previous chunk's *full* base.  Lossy
        # codecs are excluded: a delta restores the exact canonical bytes,
        # which would silently change int8 round-trip semantics.  A run of
        # rebase_every consecutive deltas forces a full write so one base
        # object never underpins the whole retention window.
        with self._lock:
            run = self._delta_runs.get((unit, kind), 0)
        if (self.delta and delta_base and run < self.rebase_every
                and codec in ("none", "zstd")):
            try:
                base_digest = delta_base
                info = self.object_info(base_digest)
                if info["stored"] != "full" and info["base"]:
                    base_digest = info["base"]  # delta or block_delta
                base_canon = self.read_canonical(base_digest)
            except (FileNotFoundError, serial.ChunkCorruption,
                    compression.CodecUnavailable):
                # unreadable base (missing, corrupt, or written with a
                # codec this environment lacks): degrade to a full write
                base_canon = None
            if base_canon is not None:
                with tracing.span("ckpt.write.encode"):
                    dblob = self.dispatch.call(
                        "delta_encode", canon, base_canon,
                        "zstd" if codec == "zstd" else "none")
                if len(dblob) < self.delta_ratio * len(full_payload):
                    nbytes = self._write_object(digest, {
                        "v": OBJECT_VERSION, "format": "delta",
                        "base": base_digest, "payload": dblob})
                    self._canon_remember(digest, canon)
                    with self._lock:
                        self._delta_runs[(unit, kind)] = run + 1
                    self._bump(written_bytes=nbytes, delta_chunks=1)
                    return ChunkRef(step=step, unit=unit, kind=kind,
                                    relpath=self.object_relpath(digest),
                                    nbytes=nbytes, digest=digest,
                                    stored="delta", delta_base=base_digest)

        nbytes = self._write_object(digest, {
            "v": OBJECT_VERSION, "format": "full", "codec": codec,
            "base": None, "payload": full_payload})
        self._canon_remember(digest, canon)
        with self._lock:
            self._delta_runs[(unit, kind)] = 0
        self._bump(written_bytes=nbytes, full_chunks=1)
        return ChunkRef(step=step, unit=unit, kind=kind,
                        relpath=self.object_relpath(digest), nbytes=nbytes,
                        digest=digest, stored="full", delta_base=None)

    # ---- fingerprint-pipeline io ----
    def write_fp(self, step: int, unit: str, kind: str,
                 packet: "fputil.FingerprintPacket",
                 *, prev_ref: Optional[ChunkRef] = None) -> ChunkRef:
        """Persist a unit from a fingerprint packet (see saver): either a
        full object rebuilt from raw leaf bytes, or a block-sparse delta
        holding only the dirty blocks — the full canonical payload is
        never materialized on the delta path.  The saver makes the
        full-vs-delta decision (it owns the device-side dirty information);
        this method handles dedup, framing, atomic write, and delta-run
        accounting."""
        digest = packet.digest
        self._bump(logical_bytes=packet.logical_bytes,
                   hashed_bytes=len(packet.table))
        claim = self._claim(digest)
        if claim is None:
            return self._dedup_ref(step, unit, kind, digest,
                                   prev_ref=prev_ref)
        try:
            table = fputil.unpack_table(packet.table)
            if packet.full:
                # Encode straight from the packet's raw leaf bytes — no
                # tree rebuild — via the dispatch (subprocess worker under
                # the process backend).  Leaves arrive in flatten order,
                # so the payload is byte-identical to
                # ``encode_chunk(rebuild_full(leaves))``.
                with tracing.span("ckpt.write.encode"):
                    items = [(l.path, tuple(l.shape), l.dtype,
                              bytes(l.data[:l.nbytes]))
                             for l in packet.leaves]
                    payload = self.dispatch.call("encode_chunk_items",
                                                 items, {}, self.codec)
                env = {"v": OBJECT_VERSION, "format": "full",
                       "codec": self.codec, "base": None, "payload": payload,
                       "fp": packet.table}
                nbytes = self._write_object(digest, env)
                with self._lock:
                    self._delta_runs[(unit, kind)] = 0
                    self._fp_tables[digest] = table
                self._bump(written_bytes=nbytes, full_chunks=1)
                return ChunkRef(step=step, unit=unit, kind=kind,
                                relpath=self.object_relpath(digest),
                                nbytes=nbytes, digest=digest, stored="full",
                                delta_base=None)
            assert packet.base_digest, "block delta requires a base"
            with tracing.span("ckpt.write.encode"):
                records = [
                    {"name": l.path, "shape": list(l.shape),
                     "dtype": l.dtype, "nbytes": l.nbytes,
                     "block": l.block_bytes,
                     "idx": [] if l.idx is None else list(map(int, l.idx)),
                     # staged payloads arrive as memoryviews into a
                     # staging slot; materialize on THIS (writer) thread
                     "data": (l.data if isinstance(l.data, bytes)
                              else bytes(l.data))}
                    for l in packet.leaves if l.idx is None or len(l.idx)]
                blob = self.dispatch.call(
                    "block_delta_encode", records,
                    "zstd" if self.codec == "zstd" else "none")
            env = {"v": OBJECT_VERSION, "format": "block_delta",
                   "base": packet.base_digest, "payload": blob,
                   "fp": packet.table}
            nbytes = self._write_object(digest, env)
            with self._lock:
                run = self._delta_runs.get((unit, kind), 0)
                self._delta_runs[(unit, kind)] = run + 1
                self._fp_tables[digest] = table
            self._bump(written_bytes=nbytes, delta_chunks=1)
            return ChunkRef(step=step, unit=unit, kind=kind,
                            relpath=self.object_relpath(digest),
                            nbytes=nbytes, digest=digest, stored="delta",
                            delta_base=packet.base_digest)
        finally:
            with self._lock:
                self._inflight.pop(digest, None)
            claim.set()

    def load_fp_table(self, digest: str) -> Optional[list]:
        """The fingerprint table of an fp-addressed object (None for
        canonical-digest objects).  Cached in memory: after a process
        restart the first save per unit pays one envelope read to recover
        the reference vector — the same cold-cache cost the canonical
        pipeline pays for its delta base."""
        with self._lock:
            tbl = self._fp_tables.get(digest)
        if tbl is not None:
            return tbl
        if not self.has(digest):
            return None
        try:
            env = self._read_envelope(digest)
        except serial.ChunkCorruption:
            return None
        blob = env.get("fp")
        if blob is None:
            return None
        try:
            tbl = fputil.unpack_table(blob)
        except ValueError:
            return None
        with self._lock:
            self._fp_tables[digest] = tbl
        return tbl

    def delta_run(self, unit: str, kind: str) -> int:
        """Consecutive delta objects written for this unit since its last
        full — the saver consults it to force periodic rebases."""
        with self._lock:
            return self._delta_runs.get((unit, kind), 0)

    def note_dedup(self, step: int, unit: str, kind: str, digest: str,
                   *, prev_ref: Optional[ChunkRef] = None,
                   logical_bytes: int = 0) -> ChunkRef:
        """Account a saver-detected dedup hit (fingerprints matched on
        device, so no payload was transferred or hashed)."""
        self._bump(logical_bytes=logical_bytes)
        return self._dedup_ref(step, unit, kind, digest, prev_ref=prev_ref)

    def seed_delta_runs(self, runs: Dict[Tuple[str, str], int]) -> None:
        """Resume per-unit consecutive-delta counts (derived from the
        manifest chain) so the rebase_every bound survives restarts."""
        with self._lock:
            self._delta_runs = dict(runs)

    # ---- refcounts / gc ----
    def set_refcounts(self, counts: Counter) -> None:
        with self._lock:
            self._refcounts = Counter(counts)

    def incref(self, digests: Iterable[str]) -> None:
        with self._lock:
            for d in digests:
                self._refcounts[d] += 1

    def decref(self, digests: Iterable[str]) -> None:
        with self._lock:
            for d in digests:
                self._refcounts[d] -= 1

    def refcount(self, digest: str) -> int:
        with self._lock:
            return self._refcounts.get(digest, 0)

    def gc_objects(self) -> int:
        """Delete objects with no remaining references; returns bytes freed.

        Objects absent from the refcount map (orphans from an interrupted
        save) are also swept, as are crash-leftover ``*.tmp-*`` files from
        each tier's atomic-write protocol (``backend.sweep_tmp`` — every
        tier sweeps its own temporaries and never touches committed
        objects in another tier) — only call after the current manifest
        has been committed and increffed, and never concurrently with
        writes.
        """
        freed = self.backend.sweep_tmp()
        for digest in list(self.iter_digests()):
            if self.refcount(digest) > 0:
                continue
            reclaimed = self.backend.delete(digest)
            if reclaimed == 0:
                continue
            freed += reclaimed
            with self._lock:
                self._info.pop(digest, None)
                self._refcounts.pop(digest, None)
                self._fp_tables.pop(digest, None)
                old = self._canon_cache.pop(digest, None)
                if old is not None:
                    self._canon_cache_bytes -= len(old)
            if self.block_cache is not None:
                self.block_cache.discard(digest)
        return freed

    # ---- usage / tier passthroughs ----
    def object_size(self, digest: str) -> int:
        return self.backend.size(digest)

    def total_bytes(self) -> int:
        return sum(self.backend.size(d) for d in self.iter_digests())

    def drain_spill(self) -> None:
        """Durability barrier: block until every object written so far
        has reached the backend's durable tier (no-op off-tiered)."""
        self.backend.drain()

    def pending_spill(self) -> int:
        return self.backend.pending_spill()

    def tier_stats(self) -> Dict[str, int]:
        return self.backend.tier_stats()

    def durability(self) -> Dict[str, Any]:
        """What the manifest-commit barrier records: which backend this
        event's objects live on, the deepest durability level every
        object has reached (``durable_on``), and — for compositions with
        a best-effort tier — whether the commit is degraded (remote
        replication still owed).  Tiered backends answer recursively."""
        d = dict(self.backend.durability())
        d["backend"] = self.backend.name
        return d

    def close(self) -> None:
        self.backend.close()

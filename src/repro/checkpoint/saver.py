"""CheckpointManager — LLMTailor's selective, layer-wise checkpoint system.

Save path (fingerprint pipeline, the default — see docs/perf.md):
  1. the policy picks this event's layer units,
  2. for each selected unit, a Pallas kernel reduces the device-resident
     tensors to per-64KiB-block checksum pairs (~0.02% of the data) and
     compares them on device against the unit's previous vector:
     - unchanged unit: resolves as a dedup hit by its stored digest with
       ZERO payload device->host transfer and zero payload hashing,
     - drifted unit: only the dirty blocks are gathered to host; the full
       payload moves only when no usable base exists (first event, rebase,
       or dirty fraction too high),
  3. the writer threads turn each packet into an object — a block-sparse
     delta (dirty blocks only) or a full chunk — while the training thread
     is already fingerprinting/gathering the next unit (pipeline overlap);
     under ``store_backend="tiered"`` the object lands in the hot RAM
     tier and the shared transfer pool's spill lane copies it to the
     durable tier in the background (docs/storage.md),
  4. after all chunks land (on the fast tier at least; ``spill_barrier``
     upgrades that to the durable tier), the manifest commits: every unit
     maps to the digest of the newest chunk holding it (units skipped
     this event keep their previous refs — the implicit Frankenstein
     merge), and ``meta["storage"]`` records which tier the event was
     durable on at commit time,
  5. refcounted GC: manifests beyond the retention window release their
     references and objects with no remaining references are deleted
     (from every tier).

``fingerprint=False`` selects the legacy full-gather path: device_get of
the whole unit, blake2b over the canonical payload, XOR delta in the
store.  Both paths' objects coexist in one store and restore uniformly.

Shard-native saves (``repro.checkpoint.sharded``, docs/storage.md) run
the same pipeline per *participant* over only its owned index blocks —
one shard object per (unit, kind, participant) — and replace step 4's
manifest commit with a two-phase barrier; manifest entries then hold
shard SETS that restore through the same engine (slice-aware plans).

Restore path (= the paper's merge, done lazily — see docs/restore.md):
  ``restore`` delegates to the planned, pipelined engine in
  ``repro.checkpoint.restore``: a planner resolves the manifest chain
  into a deduplicated read plan (each object digest read once, delta
  bases cached, older-manifest fallbacks enumerated up front), and a
  streaming executor overlaps chunk read + decompress + verify with
  per-unit ``jax.device_put`` onto the target shardings.  Partial
  restore (``parts=("params",)``, unit-prefix filters) reads only the
  objects the caller asked for; on a corrupt/missing chunk a unit falls
  back to its previous manifest entry (degraded-but-resumable, logged,
  and recorded in ``last_restore_stats["fallback_units"]``).
"""
from __future__ import annotations

import logging
from collections import Counter
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import faults, tracing
from repro.checkpoint import fingerprint as fputil
from repro.checkpoint.async_io import (
    WORKER_BACKENDS,
    AsyncWriter,
    PendingResult,
    TransferPool,
)
from repro.checkpoint.backends import StorageBackend, make_backend
from repro.checkpoint.block_cache import BlockCache
from repro.checkpoint.chunk_store import ChunkRef, ChunkStore
from repro.checkpoint.restore import (  # noqa: F401 - RestoreError re-export
    DEFAULT_IO_THREADS,
    PARTS_ALL,
    RestoreEngine,
    RestoreError,
)
from repro.checkpoint.serial import flatten_with_paths
from repro.checkpoint.sharded import WantedFn, _usable_prev
from repro.core.layer_registry import LayerRegistry
from repro.core.manifest import (
    Manifest,
    ManifestStore,
    entry_refs,
    is_sharded,
)
from repro.core.policies import CheckpointPolicy, PolicyContext
from repro.kernels import block_fp as bfp

log = logging.getLogger("repro.checkpoint")

PyTree = Any


def tables_to_host(cur: List[bfp.LeafFP]) -> List[bfp.LeafFP]:
    """``block_fp.tree_to_host``, counted: one batched ``device_get`` per
    (unit, kind)."""
    tracing.count("d2h_calls")
    return bfp.tree_to_host(cur)


class CheckpointManager:
    def __init__(
        self,
        root: Path | str,
        registry: LayerRegistry,
        policy: CheckpointPolicy,
        *,
        codec: str = "auto",
        async_save: bool = True,
        keep: int = 8,
        writer_threads: int = 2,
        delta: bool = True,
        fingerprint: bool = True,
        fp_block_bytes: int = fputil.DEFAULT_BLOCK_BYTES,
        fp_max_dirty_frac: float = 0.5,
        restore_threads: int = DEFAULT_IO_THREADS,
        restore_verify: bool = True,
        store_backend: "str | StorageBackend" = "local",
        spill_threads: int = 2,
        hot_budget_bytes: Optional[int] = None,
        spill_barrier: bool = False,
        remote_opts: Optional[Dict[str, Any]] = None,
        io_backend: str = "thread",
        io_workers: Optional[int] = None,
        block_cache: Optional[BlockCache] = None,
        block_cache_bytes: Optional[int] = None,
        block_cache_shm: bool = False,
    ):
        self.root = Path(root)
        self.registry = registry
        self.policy = policy
        # One transfer pool carries BOTH the saver's chunk-write lane and
        # the tiered backend's spill lane (instead of private pools per
        # producer): write drains never wait on spill, but the threads —
        # the actual IO resource — are shared and bounded.  A caller who
        # passes a pre-composed StorageBackend INSTANCE keeps whatever
        # pool that instance was built with (pass pool= to TieredBackend
        # to share one explicitly); the saver then only sizes its own
        # write lane and the spill_threads knob does not apply.
        own_composition = isinstance(store_backend, StorageBackend)
        tiered = (not own_composition) and store_backend in ("tiered",
                                                             "remote3")
        # remote3 runs TWO spill lanes (RAM→disk and disk→remote) on the
        # shared pool, so it gets a second helping of spill threads.
        spill_lanes = 2 if store_backend == "remote3" else 1
        if io_backend not in WORKER_BACKENDS:
            raise ValueError(
                f"io_backend must be one of {WORKER_BACKENDS}, "
                f"got {io_backend!r}")
        self.transfer_pool: Optional[TransferPool] = None
        # ``io_backend="process"`` always needs a pool (it owns the
        # subprocess worker fleet and the shared-memory arena), even for
        # synchronous saves — the hot byte work still offloads.
        if async_save or tiered or io_backend == "process":
            # The queue is bounded (write-lane backpressure on the
            # training thread) EXCEPT when the pool also carries the
            # spill lane: write tasks then submit spill tasks, and a
            # bounded queue could deadlock with every worker blocked on
            # a full put (see TransferPool).
            self.transfer_pool = TransferPool(
                writer_threads + (spill_threads * spill_lanes
                                  if tiered else 0),
                max_queue=0 if tiered else 64,
                worker_backend=io_backend,
                io_workers=io_workers)
        dispatch = (self.transfer_pool.dispatch
                    if self.transfer_pool is not None else None)
        backend = make_backend(store_backend, self.root,
                               pool=self.transfer_pool,
                               spill_threads=spill_threads,
                               hot_budget_bytes=hot_budget_bytes,
                               remote_opts=remote_opts,
                               dispatch=dispatch)
        # Digest-keyed host-RAM object cache underneath backend reads —
        # the serving-fleet knob (docs/serving.md): pass an existing
        # ``block_cache`` to share one across managers/variants, or
        # ``block_cache_bytes`` to have this manager own a fresh one
        # (``block_cache_shm`` backs its entries with /dev/shm segments
        # under the repo-wide owner-pid prefix).
        self._own_block_cache = block_cache is None \
            and block_cache_bytes is not None
        if self._own_block_cache:
            block_cache = BlockCache(int(block_cache_bytes),
                                     shm=block_cache_shm)
        self.block_cache = block_cache
        self.store = ChunkStore(self.root, codec=codec, delta=delta,
                                backend=backend, dispatch=dispatch,
                                block_cache=block_cache)
        self.manifests = ManifestStore(self.root)
        self.keep = keep
        self.async_save = async_save
        # False (default): commit the manifest as soon as every object is
        # on the FAST tier and let spill keep overlapping training — the
        # manifest records durable_on="hot".  True: wait the spill lane
        # down first, so every committed manifest is durable-tier-backed.
        self.spill_barrier = spill_barrier
        self.restorer = RestoreEngine(self.store, self.manifests, registry,
                                      io_threads=restore_threads,
                                      verify=restore_verify)
        self.fingerprint = fingerprint
        self.fp_block_bytes = fp_block_bytes
        # Above this dirty fraction a block-sparse delta stops paying (the
        # index overhead plus a near-full payload) — gather everything and
        # write a full object instead.
        self.fp_max_dirty_frac = fp_max_dirty_frac
        self.writer = (AsyncWriter(pool=self.transfer_pool)
                       if async_save else None)
        self._event_index = self._infer_event_index()
        self._rebuild_refcounts()
        # (unit, kind) -> device fingerprint vector of the content behind
        # the last COMMITTED manifest entry (advanced only after a commit,
        # so a failed event can never make a stale entry look current).
        self._fp_refs: Dict[Tuple[str, str], Any] = {}
        self.last_save_stats: Dict[str, Any] = {}

    def _infer_event_index(self) -> int:
        """Resume the event counter across restarts from the newest
        manifest's recorded index.  Counting retained manifests instead
        would saturate at the retention cap ``keep``, freezing
        event-alternating policies (parity/interval/filtered) on one
        half forever."""
        m = self.manifests.load()
        if m is not None and "event_index" in m.meta:
            return int(m.meta["event_index"]) + 1
        return len(self.manifests.all_steps())

    def reserve_event_index(self) -> int:
        """The index the next event will commit under.  The overlapped
        saver captures it at ``begin`` (policy selection keys off the
        event counter, but the commit lands steps later) and passes it
        back through ``_commit_event(event_index=...)``."""
        return self._event_index

    def _rebuild_refcounts(self) -> None:
        """Derive object refcounts AND per-unit delta-run lengths from the
        committed manifests.

        Neither is persisted: the manifests are the single source of
        truth, so a crash between a commit and a GC can at worst leave
        unreferenced objects for the next GC to sweep.  Replaying the
        delta runs matters for durability: without it, a crash/restart
        loop would reset the rebase counter and let one full base object
        underpin the entire retention window.
        """
        counts: Counter = Counter()
        runs: Dict[Tuple[str, str], int] = {}
        last_digest: Dict[Tuple[str, str], str] = {}
        for s in self.manifests.all_steps():
            m = self.manifests.load(s)
            if m is None:
                continue
            counts.update(m.referenced_digests())
            for unit, kinds in m.entries.items():
                for kind, entry in kinds.items():
                    for ref in entry_refs(entry):
                        # Shard objects run their delta chains per
                        # participant — same namespace ShardedSaver
                        # writes under.
                        ukey = (unit if ref.spec is None else
                                f"{unit}@p{ref.spec.get('participant', 0)}")
                        key = (ukey, kind)
                        if last_digest.get(key) == ref.digest:
                            continue  # carried-over entry, not a new write
                        last_digest[key] = ref.digest
                        runs[key] = (runs.get(key, 0) + 1
                                     if ref.stored == "delta" else 0)
        self.store.set_refcounts(counts)
        self.store.seed_delta_runs(runs)

    # ------------------------------------------------------------------ save
    def save(self, state: Dict[str, PyTree], *, step: Optional[int] = None,
             meta: Optional[Dict] = None,
             drift_scores: Optional[Dict[str, float]] = None,
             units: Optional[Sequence[str]] = None,
             durability_barrier: Optional[bool] = None) -> Manifest:
        """Persist one checkpoint event and commit its manifest.

        ``units`` overrides the policy's selection for this event (the
        supervisor's preemption save captures every unit regardless of
        policy — cheap under fingerprint dedup since unchanged units
        resolve without payload movement).  ``durability_barrier``
        overrides ``self.spill_barrier`` for this event: False commits as
        soon as objects are on the fast tier — the preemption hot save —
        and True waits the spill lane down first.
        """
        trace = tracing.Event()
        with tracing.active(trace), tracing.span("ckpt.save"):
            manifest, counts = self._save_event(
                state, step=step, meta=meta, drift_scores=drift_scores,
                units=units, durability_barrier=durability_barrier)
        stages, counters = trace.fold()
        # The synchronous save blocks the caller end to end: the stall is
        # the whole event (the overlapped saver is where they diverge).
        self.last_save_stats = self._event_stats(
            **counts, stages=stages, counters=counters,
            timings={"snapshot_seconds": stages["ckpt.save.snapshot"],
                     "stage_seconds": 0.0,
                     "writeback_seconds": stages["ckpt.save.drain"],
                     "stall_seconds": stages["ckpt.save"],
                     "total_seconds": stages["ckpt.save"]})
        return manifest

    def _save_event(self, state, *, step, meta, drift_scores, units,
                    durability_barrier) -> Tuple[Manifest, Dict[str, Any]]:
        """The body of ``save`` inside its ``ckpt.save`` span: the
        manifest and the event's counts for ``_event_stats``."""
        pool = self.transfer_pool
        workers0 = (pool.dispatch.stats() if pool is not None else None)
        step = int(state["step"]) if step is None else int(step)
        ctx = PolicyContext(event_index=self._event_index, step=step,
                            drift_scores=drift_scores)
        # Pre-content-addressing manifests (digest-less refs) can't be
        # carried forward — same rule as the sharded path.
        prev = _usable_prev(self.manifests.load())
        if prev is None:
            # The very first event is always a full save: every later
            # manifest must be able to reference a complete base.
            selected = self.policy.all_units()
        elif units is not None:
            selected = list(dict.fromkeys(units))
        else:
            selected = list(dict.fromkeys(self.policy.select(ctx)))
        entries: Dict[str, Dict[str, ChunkRef]] = (
            {u: dict(k) for u, k in prev.entries.items()} if prev else {})

        # Snapshot selected units to host (sync) and enqueue writes (async).
        # The fingerprint path replaces the full device_get with a device
        # compare + dirty-block gather; while the writer threads encode and
        # write unit N's packet, this loop is already fingerprinting and
        # gathering unit N+1 — gather, encode, and write are pipelined
        # across device/PCIe, CPU, and disk.
        self.store.reset_stats()
        d2h_bytes = 0
        blocks_moved = 0
        blocks_total = 0
        kernel_leaves: Counter = Counter()
        pending: Dict[Tuple[str, str], PendingResult] = {}
        new_fps: Dict[Tuple[str, str], Any] = {}
        with tracing.span("ckpt.save.snapshot"):
            for name in selected:
                for kind in ("weights", "opt"):
                    pref = self._prev_entry(prev, name, kind)
                    if not self.fingerprint:
                        with tracing.span("ckpt.save.d2h", unit=name,
                                          kind=kind):
                            host = jax.device_get(
                                self._extract(state, name, kind))
                            tracing.count("d2h_calls")
                        faults.crash_point("gather")
                        d2h_bytes += sum(np.asarray(x).nbytes
                                         for x in jax.tree.leaves(host))
                        with tracing.span("ckpt.save.pack", unit=name,
                                          kind=kind):
                            if self.writer is not None:
                                pending[(name, kind)] = self.writer.submit(
                                    self.store.write, step, name, kind,
                                    host, prev_ref=pref)
                            else:
                                entries.setdefault(name, {})[kind] = \
                                    self.store.write(step, name, kind, host,
                                                     prev_ref=pref)
                        continue
                    res, ustat, cur = self._save_unit_fp(
                        step, name, kind,
                        lambda: self._extract(state, name, kind), pref)
                    d2h_bytes += ustat["d2h_bytes"]
                    blocks_moved += ustat["blocks_moved"]
                    blocks_total += ustat["blocks_total"]
                    kernel_leaves.update(ustat["kernel_leaves"])
                    new_fps[(name, kind)] = cur
                    if isinstance(res, PendingResult):
                        pending[(name, kind)] = res
                    else:
                        entries.setdefault(name, {})[kind] = res

        # All chunks must land (on the fast tier at least) before the
        # manifest commits; the optional spill barrier upgrades that to
        # "on the durable tier".
        with tracing.span("ckpt.save.drain"):
            if self.writer is not None:
                self.writer.drain()
                for (name, kind), p in pending.items():
                    entries.setdefault(name, {})[kind] = p.result()
        manifest, storage = self._commit_event(
            step=step, entries=entries, selected=selected, meta=meta,
            new_fps=new_fps, durability_barrier=durability_barrier)
        return manifest, dict(
            step=step, selected=selected, d2h_bytes=d2h_bytes,
            blocks_moved=blocks_moved, blocks_total=blocks_total,
            storage=storage, workers0=workers0, kernel_leaves=kernel_leaves)

    def _extract(self, state: Dict[str, PyTree], name: str,
                 kind: str) -> PyTree:
        return (self.registry.extract_unit(state["params"], name)
                if kind == "weights" else
                self.registry.extract_opt_unit(state["opt"], name))

    def _prev_entry(self, prev: Optional[Manifest], name: str,
                    kind: str) -> Optional[ChunkRef]:
        if prev is None:
            return None
        e = prev.entries.get(name, {}).get(kind)
        if e is None or is_sharded(e):
            # A previous SHARDED entry can't anchor a global-array
            # dedup/delta (different payload layout): this global
            # save starts the unit on a fresh full base.  The shard
            # set itself still carries forward for unselected units.
            return None
        return e

    def _commit_event(self, *, step: int, entries, selected, meta,
                      new_fps, event_index: Optional[int] = None,
                      durability_barrier: Optional[bool] = None
                      ) -> Tuple[Manifest, Dict[str, Any]]:
        """Barrier + manifest commit + refcount/GC bookkeeping.

        The single commit seam shared by the synchronous ``save`` and the
        overlapped saver (:mod:`repro.checkpoint.overlap`): both paths
        commit through this exact sequence, which is what makes them
        bit-exact peers — only *when* the work ran differs.

        ``event_index`` lets an overlapped event commit under the index
        reserved when it *began* (policy alternation keys off the event
        counter at selection time, steps before the commit lands); the
        counter itself only ever moves forward.
        """
        with tracing.span("ckpt.save.commit"):
            barrier = (self.spill_barrier if durability_barrier is None
                       else durability_barrier)
            if barrier:
                self.store.drain_spill()
            # The durability record is part of the commit: a reader of
            # this manifest knows which tier the event's objects were
            # durable on at commit time (e.g. durable_on="hot" while
            # spill is in flight).
            storage = self.store.durability()
            idx = (self._event_index if event_index is None
                   else int(event_index))
            manifest = Manifest(step=step, entries=entries,
                                meta=dict(meta or {}, event_index=idx,
                                          policy=self.policy.name,
                                          storage=storage),
                                saved_units=list(selected))
            # Re-saving a step overwrites its manifest file: release the
            # replaced manifest's references or its objects leak until
            # restart.
            replaced = self.manifests.load(step)
            self.manifests.commit(manifest)
            self.store.incref(manifest.referenced_digests().elements())
            if replaced is not None:
                self.store.decref(replaced.referenced_digests().elements())
            self._event_index = max(self._event_index, idx + 1)
            # The commit is durable: only now may the fingerprint
            # references advance (a failed write above raised before
            # reaching here).
            self._fp_refs.update(new_fps)
            self.gc()
        return manifest, storage

    def _event_stats(self, *, step: int, selected, d2h_bytes: int,
                     blocks_moved: int, blocks_total: int, storage,
                     workers0, kernel_leaves: Counter,
                     stages: Dict[str, float], counters: Counter,
                     timings: Dict[str, float]) -> Dict[str, Any]:
        """Assemble one event's ``last_save_stats`` dict.

        ``timings`` carries the four-way split (docs/perf.md), each read
        from the event's spans: ``snapshot_seconds`` (device
        fingerprint/gather dispatch + the decision pass),
        ``stage_seconds`` (host materialization of staged buffers),
        ``writeback_seconds`` (encode+write drain), and ``stall_seconds``
        — the time the *caller's step loop* actually blocked, the number
        the zero-stall pipeline exists to shrink.  ``stages`` holds every
        span's seconds summed over the event, ``counters`` the event's
        counters (``tracing.Event.fold``).  ``kernel_leaves`` counts the
        leaves each device path fingerprinted and gathered
        (``fingerprint.KERNEL_LEAF_KEYS``).
        """
        pool = self.transfer_pool
        io = dict(self.store.stats)
        if blocks_total:
            dirty_frac = blocks_moved / blocks_total
        else:
            dirty_frac = 1.0 if not self.fingerprint else 0.0
        stats = {
            "step": step,
            "selected_units": len(selected),
            "total_units": len(self.registry.units),
            **timings,
            "stages": stages,
            # transfer/hash accounting for this event (the fingerprint win)
            "d2h_bytes": d2h_bytes,
            "d2h_calls": counters["d2h_calls"],
            # host copies of payload bytes after the fetch (docs/perf.md)
            "payload_copy_bytes": counters["payload_copy_bytes"],
            "hashed_bytes": io["hashed_bytes"],
            "dirty_block_frac": dirty_frac,
            # dedup/delta accounting for this event
            "logical_bytes": io["logical_bytes"],
            "written_bytes": io["written_bytes"],
            "dedup_hits": io["dedup_hits"],
            "delta_chunks": io["delta_chunks"],
            "full_chunks": io["full_chunks"],
            # tier accounting (what the manifest recorded at commit time)
            "backend": storage["backend"],
            "durable_on": storage["durable_on"],
            "spill_pending": storage["pending_spill"],
            # which worker backend ran the byte work (hash/codec/write)
            "io_backend": (pool.dispatch.backend if pool is not None
                           else "thread"),
            **{k: kernel_leaves.get(k, 0) for k in fputil.KERNEL_LEAF_KEYS},
        }
        if workers0 is not None:
            # Process backend: this event's share of the subprocess
            # worker traffic, per lane (write vs spill vs ...).
            w1 = pool.dispatch.stats()
            lanes: Dict[str, Dict[str, int]] = {}
            for lane, s1 in w1["lanes"].items():
                s0 = workers0["lanes"].get(lane,
                                           {"tasks": 0, "bytes_shm": 0})
                d = {"tasks": s1["tasks"] - s0["tasks"],
                     "bytes_shm": s1["bytes_shm"] - s0["bytes_shm"]}
                if d["tasks"]:
                    lanes[lane] = d
            stats["workers"] = {
                "lanes": lanes,
                "worker_restarts": w1["worker_restarts"],
            }
        return stats

    def _save_unit_fp(self, step: int, name: str, kind: str,
                      extract: Callable[[], PyTree],
                      pref: Optional[ChunkRef]):
        """Fingerprint save path for one (unit, kind).

        ``extract`` returns the unit's tree; it is called inside the
        ``ckpt.save.fingerprint`` span, so slicing the unit out of the
        stacked state counts there.  Returns ``(ref_or_pending, stats,
        cur_fp)`` where stats counts the payload bytes/blocks that
        actually crossed device->host.  The fingerprint vectors
        themselves (~0.02% of the data) are not counted as payload."""
        bb = self.fp_block_bytes
        attrs = {"unit": name, "kind": kind}
        with tracing.span("ckpt.save.fingerprint", **attrs):
            tree = extract()
            cur = bfp.fingerprint_tree(tree, block_bytes=bb)
            faults.crash_point("fingerprint")
            nb_total = sum(l.n_blocks for l in cur)
            logical = sum(l.nbytes for l in cur)
            stats = {"d2h_bytes": 0, "blocks_moved": 0,
                     "blocks_total": nb_total,
                     "kernel_leaves": Counter(
                         {f"fp_leaves_{bfp.kernel_path()}": len(cur)})}

            # Reference vector for the content behind the previous
            # manifest entry: device-resident from the last commit, or
            # (after a process restart) the table stored in that object's
            # envelope.
            ref_fp = self._fp_refs.get((name, kind))
            if ref_fp is None and pref is not None and pref.digest:
                ref_fp = self.store.load_fp_table(pref.digest)
            if (ref_fp is not None and pref is not None and pref.digest
                    and bfp.leaves_match(cur, ref_fp)):
                # Unchanged: dedup by the stored digest — no payload D2H,
                # no payload hash, no write.
                return (self.store.note_dedup(step, name, kind, pref.digest,
                                              prev_ref=pref,
                                              logical_bytes=logical),
                        stats, cur)

            host = tables_to_host(cur)
            tblob = fputil.pack_table(host)
            digest = fputil.fp_digest(tblob)
            if self.store.has(digest):
                # Content reverted to (or collided with) an object
                # already on disk: still zero payload transfer.
                return (self.store.note_dedup(step, name, kind, digest,
                                              prev_ref=pref,
                                              logical_bytes=logical),
                        stats, cur)

            # Delta decision (the saver owns it: only it sees the
            # device-side dirty information).  The base is the previous
            # entry's full object, exactly like the v1 XOR chain, and the
            # same rebase_every bound forces periodic fulls.
            flat = flatten_with_paths(tree)
            base_digest, base_tbl = self._delta_base(name, kind, pref, host)
            use_delta = base_tbl is not None
            dirty = None
            if use_delta:
                dirty = [bfp.dirty_block_indices(h, b)
                         for h, b in zip(host, base_tbl)]
                if (sum(len(d) for d in dirty)
                        > self.fp_max_dirty_frac * nb_total):
                    use_delta = False
        # Enqueue all device-side gathers first, then one batched
        # device_get for the whole unit — L leaves cost one D2H round
        # trip, not L.
        with tracing.span("ckpt.save.d2h", **attrs):
            if use_delta:
                gathered = jax.device_get(
                    [bfp.gather_blocks(jnp.asarray(arr), idx, block_bytes=bb)
                     if len(idx) else None
                     for (_, arr), idx in zip(flat, dirty)])
            else:
                host_arrs = jax.device_get([arr for _, arr in flat])
            tracing.count("d2h_calls")
        with tracing.span("ckpt.save.pack", **attrs):
            leaves = []
            if use_delta:
                for (path, _), leaf, idx, g in zip(flat, host, dirty,
                                                   gathered):
                    data = b""
                    if g is not None:
                        data = fputil.byte_view(g)
                        stats["d2h_bytes"] += data.nbytes
                        stats["blocks_moved"] += len(idx)
                        stats["kernel_leaves"]["gather_leaves_xla"] += 1
                    leaves.append(fputil.LeafPayload(
                        path=path, shape=leaf.shape, dtype=leaf.dtype,
                        nbytes=leaf.nbytes, block_bytes=bb, idx=idx,
                        data=data))
                packet = fputil.FingerprintPacket(
                    digest=digest, table=tblob, leaves=leaves, full=False,
                    base_digest=base_digest, logical_bytes=logical)
            else:
                for (path, _), leaf, arr in zip(flat, host, host_arrs):
                    data = fputil.byte_view(arr)
                    stats["d2h_bytes"] += data.nbytes
                    leaves.append(fputil.LeafPayload(
                        path=path, shape=leaf.shape, dtype=leaf.dtype,
                        nbytes=leaf.nbytes, block_bytes=bb, idx=None,
                        data=data))
                stats["blocks_moved"] += nb_total
                packet = fputil.FingerprintPacket(
                    digest=digest, table=tblob, leaves=leaves, full=True,
                    base_digest=None, logical_bytes=logical)
            # The unit's payload has fully crossed device->host; nothing
            # has been written yet — the canonical "died after gather"
            # drill.
            faults.crash_point("gather")
            if self.writer is not None:
                return (self.writer.submit(self.store.write_fp, step, name,
                                           kind, packet, prev_ref=pref),
                        stats, cur)
            return (self.store.write_fp(step, name, kind, packet,
                                        prev_ref=pref),
                    stats, cur)

    def _delta_base(self, name: str, kind: str, pref: Optional[ChunkRef],
                    metas) -> Tuple[Optional[str], Optional[list]]:
        """Structurally usable delta base for (unit, kind), or
        ``(None, None)``: the previous entry must be digest-addressed,
        the store codec lossless (a block delta patches exact bytes onto
        its base, which a lossy base cannot provide — exactly like the
        v1 XOR chain), the per-unit rebase bound unspent, and the base's
        stored fingerprint table meta-comparable with ``metas``.

        ``metas`` only needs paths/shapes/dtypes/nbytes/block_bytes
        (``LeafFP.meta_matches`` never reads the checksum content), so
        the overlapped saver can plan a base from tree structure alone —
        before any fingerprint has crossed to host."""
        if not (self.store.delta and pref is not None and pref.digest
                and self.store.codec in ("none", "zstd")
                and self.store.delta_run(name, kind)
                < self.store.rebase_every):
            return None, None
        base_digest = (pref.digest if pref.stored == "full"
                       else pref.delta_base)
        base_tbl = (self.store.load_fp_table(base_digest)
                    if base_digest else None)
        if (base_tbl is None or len(base_tbl) != len(metas)
                or not all(m.meta_matches(b)
                           for m, b in zip(metas, base_tbl))):
            return None, None  # no comparable base: write full
        if (self.store.object_info(base_digest).get("codec")
                not in (None, "none", "zstd")):
            return None, None  # lossy base cannot anchor exact patches
        return base_digest, base_tbl

    # --------------------------------------------------------------- restore
    def restore(self, state_like: Dict[str, PyTree], *,
                step: Optional[int] = None,
                shardings: Optional[Dict[str, PyTree]] = None,
                parts: Tuple[str, ...] = PARTS_ALL,
                units: Optional[Tuple[str, ...]] = None,
                pipelined: bool = True,
                owned: Optional[WantedFn] = None,
                manifest: Optional[Manifest] = None) -> Dict[str, PyTree]:
        """Rebuild a train state from the manifest chain (the implicit
        merge) via the streaming restore engine — thin wrapper over
        :class:`repro.checkpoint.restore.RestoreEngine`.

        ``state_like`` supplies structure/dtypes (arrays or
        ShapeDtypeStructs) for the requested ``parts``; ``shardings``
        optionally places every unit on a mesh as it streams in (elastic
        restart onto any device count).  ``parts=("params",)`` restores
        weights without optimizer state (reading strictly fewer bytes);
        ``units`` filters by unit-name prefix; ``owned`` restricts
        sharded entries to the shard objects overlapping the caller's
        slices (see ``repro.checkpoint.sharded.participant_wanted``);
        ``pipelined=False`` forces the strictly sequential executor.
        Per-restore accounting lands in ``last_restore_stats``.
        """
        return self.restorer.restore(state_like, step=step,
                                     shardings=shardings, parts=parts,
                                     units=units, pipelined=pipelined,
                                     owned=owned, manifest=manifest)

    @property
    def last_restore_stats(self) -> Dict[str, Any]:
        """Stats of the most recent ``restore`` (wall seconds, bytes/
        objects read, dedup savings, per-unit fallback provenance)."""
        return self.restorer.last_stats

    def restore_meta(self, step: Optional[int] = None) -> Dict:
        m = self.manifests.load(step)
        return dict(m.meta) if m else {}

    # ------------------------------------------------------------------- gc
    def gc(self) -> int:
        """Refcounted retention: keep the last ``keep`` manifests; dropped
        manifests release their object references and unreferenced objects
        are deleted.  Returns bytes freed."""
        steps = self.manifests.all_steps()
        for s in steps[:-self.keep] if len(steps) > self.keep else []:
            m = self.manifests.load(s)
            self.manifests.delete(s)
            if m is not None:
                self.store.decref(m.referenced_digests().elements())
        return self.store.gc_objects()

    def drain_spill(self) -> None:
        """Durability barrier: returns once every written object is on
        the durable tier (no-op for single-tier backends)."""
        self.store.drain_spill()

    def scrub(self, *, repair: bool = True) -> Dict[str, Any]:
        """Store-wide integrity scrub & repair (fsck) over every
        committed manifest; returns the machine-readable report.  See
        :class:`repro.checkpoint.scrub.StoreScrubber`."""
        from repro.checkpoint.scrub import StoreScrubber
        return StoreScrubber(self.store, self.manifests).scrub(
            repair=repair)

    def close(self) -> None:
        if self.writer is not None:
            self.writer.close()
        # Backend close drains the spill lane first (pending spills are
        # never abandoned), then the shared transfer pool goes down.
        self.store.close()
        if self.transfer_pool is not None:
            self.transfer_pool.close()
        # Only a cache this manager created is closed here — a shared
        # cache outlives any one manager by design.
        if self._own_block_cache and self.block_cache is not None:
            self.block_cache.close()

    # -------------------------------------------------------------- metrics
    def disk_usage(self) -> Dict[str, int]:
        total = 0
        objects = 0
        for d in self.store.iter_digests():
            total += self.store.object_size(d)
            objects += 1
        return {"total": total, "objects": objects,
                "manifests": len(self.manifests.all_steps())}

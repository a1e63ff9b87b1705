"""Zero-stall checkpointing: the overlapped snapshot/writeback pipeline
(docs/perf.md).

The synchronous ``CheckpointManager.save`` blocks the training thread for
the whole event: fingerprint, gather, D2H, encode, write, commit.  This
module detaches everything but the device-side dispatch from the step
that triggered the event (DataStates-LLM's lazy async snapshot) and
slices the host-side work across the next ``spread_steps`` steps
(GoCkpt's multi-step budget):

``begin(state, step)``  — the only window that touches the live (soon to
    be donated) train state.  Per selected unit it dispatches the fused
    ``block_gather`` kernel (fingerprint + compare-vs-base + dirty-block
    compaction in one device pass, capacity chosen by the advisory
    :class:`DirtyPredictor`) or — when no delta base is usable — device
    copies of the full leaves, issues the async D2H on those NEW buffers,
    and makes the exact dedup/delta decisions the sync path makes.  By
    return, training may donate the state: nothing later reads it.

``tick()``  — called once per training step.  Each tick materializes one
    spread slice's units from the in-flight D2H into a pinned
    ``StagingArena`` slot (double-buffered: unit N+1 stages while unit
    N's write drains) and submits the writes.  The tick that empties the
    queue drains the writer and commits through the SAME
    ``CheckpointManager._commit_event`` seam as a sync save.

``finish()``  — forces the event to completion now (preemption saves,
    shutdown, or a new ``begin`` arriving mid-spread: events are strictly
    FIFO, never concurrent).

Invariants:

- **Prediction is advisory, the fingerprint compare is authoritative.**
  The predictor only sizes the kernel's compacted buffer.  Predicted-
  dirty-but-clean costs a wasted on-device gather (no D2H, no write);
  predicted-clean-but-dirty overflows the buffer, which the kernel's
  count reports, and ``begin`` re-dispatches at the true size.
  Mispredictions cost bandwidth, never bytes in the checkpoint.
- **Bit-exactness.** Decision order, packet bytes, digests, and the
  commit sequence replicate the sync path exactly; an overlapped save
  and a sync save of the same state commit identical manifests
  (``tests/test_overlap.py`` property-tests this, including under
  injected mispredictions).
- **Crash mid-overlap loses nothing.** No manifest commits until the
  last slice; the ``snapshot_overlap`` / ``spread_slice`` crash points
  sit inside the new windows and the crash matrix asserts the previous
  manifest stays LATEST with a bit-exact restore.
- **No interleaved commits.**  While an event is in flight the manager
  must not commit other manifests; ``begin``/``finish`` enforce FIFO for
  overlapped events and callers route direct ``save`` calls through
  ``finish`` first (the trainer does).  A violation is detected at
  commit time and the carried entries re-anchor on the newest manifest.
"""
from __future__ import annotations

import dataclasses
import logging
import math
from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import faults, tracing
from repro.checkpoint import fingerprint as fputil
from repro.checkpoint.async_io import PendingResult, StagingArena
from repro.checkpoint.saver import CheckpointManager, tables_to_host
from repro.checkpoint.sharded import _usable_prev
from repro.checkpoint.serial import flatten_with_paths
from repro.core.manifest import Manifest
from repro.core.policies import PolicyContext
from repro.kernels import block_fp as bfp
from repro.kernels import block_gather as bgather
from repro.kernels.block_fp.ref import LeafFP

log = logging.getLogger("repro.checkpoint")

PyTree = Any


class DirtyPredictor:
    """Advisory per-leaf dirty-block predictor.

    Seeds the fused kernel's static gather capacity from the signals
    already on hand: the leaf's dirty count last event (optimizer state
    touches a stable working set between events) scaled by ``margin``,
    widened further when the unit's drift score (DeltaTracker, gradient/
    optimizer-magnitude derived) says this event moved more than the
    last.  First sight of a leaf predicts everything dirty — the only
    guess that can't overflow.  Wrong guesses are harmless by
    construction (see module docstring); the payoff of a right guess is
    a compacted D2H buffer sized to the drift instead of the model.
    """

    def __init__(self, margin: float = 1.5):
        self.margin = float(margin)
        self._last: Dict[Tuple[str, str, str], int] = {}
        self.hits = 0
        self.overflows = 0

    def predict(self, name: str, kind: str, path: str, n_blocks: int,
                drift: Optional[float]) -> int:
        last = self._last.get((name, kind, path))
        if last is None:
            return n_blocks
        scale = self.margin * (1.0 + min(max(drift or 0.0, 0.0), 1.0))
        return min(n_blocks, max(1, math.ceil(last * scale)))

    def observe(self, name: str, kind: str, path: str, count: int) -> None:
        self._last[(name, kind, path)] = int(count)


@dataclasses.dataclass
class _StagedLeaf:
    meta: LeafFP                    # path/shape/dtype/nbytes/block_bytes
    mode: str                       # "delta" | "full"
    dev: Any                        # staged device buffer (D2H in flight)
    idx: Optional[np.ndarray] = None   # delta: dirty indices (host, exact)
    count: int = 0                  # delta: dirty blocks staged


@dataclasses.dataclass
class _StagedUnit:
    name: str
    kind: str
    pref: Any                       # previous ChunkRef (or None)
    digest: str
    tblob: bytes
    logical: int
    nb_total: int
    full: bool                      # write mode when not dedup'd
    base_digest: Optional[str]
    leaves: List[_StagedLeaf]


@dataclasses.dataclass
class _Event:
    step: int
    event_index: int
    prev_step: Optional[int]
    entries: Dict[str, Dict[str, Any]]
    selected: List[str]
    meta: Optional[Dict]
    durability_barrier: Optional[bool]
    queue: List[_StagedUnit]
    per_slice: int
    trace: tracing.Event
    resolved: Dict[Tuple[str, str], Any] = dataclasses.field(
        default_factory=dict)
    pending: Dict[Tuple[str, str], PendingResult] = dataclasses.field(
        default_factory=dict)
    new_fps: Dict[Tuple[str, str], Any] = dataclasses.field(
        default_factory=dict)
    snapshot_fps: Dict[str, List[LeafFP]] = dataclasses.field(
        default_factory=dict)
    workers0: Any = None
    slices: int = 0
    d2h_bytes: int = 0
    staged_bytes: int = 0
    blocks_moved: int = 0
    blocks_total: int = 0
    overflows: int = 0
    kernel_leaves: Counter = dataclasses.field(default_factory=Counter)


def _byte_view(arr: np.ndarray) -> np.ndarray:
    """Flat uint8 view of an array's exact bytes — extension dtypes
    (bfloat16) don't expose a ``memoryview``-castable buffer format, a
    uint8 view always does."""
    return np.ascontiguousarray(arr).view(np.uint8).reshape(-1)


def _device_copy(arrs: Sequence[jax.Array]) -> Tuple[jax.Array, ...]:
    """Fresh device buffers for the full-save path: the originals belong
    to the train state and are donated to the next step, so the staged
    copies must be NEW arrays the async D2H can read at leisure."""
    return _jit_copy(tuple(arrs))


@jax.jit
def _jit_copy(arrs):
    return tuple(jnp.copy(a) for a in arrs)


class OverlappedSaver:
    """Drives overlapped checkpoint events against a
    :class:`CheckpointManager` (which must run the fingerprint pipeline;
    the legacy full-gather path has no device-side compare to overlap).

    One instance per manager; events are strictly FIFO.  The manager's
    ``last_save_stats`` is populated at commit with the same keys as a
    sync save plus the overlap extras (``save_mode``, ``spread_*``,
    prediction counters).
    """

    def __init__(self, mgr: CheckpointManager, *, spread_steps: int = 2,
                 staging_slots: int = 2, margin: float = 1.5,
                 interpret: Optional[bool] = None):
        if not mgr.fingerprint:
            raise ValueError(
                "overlapped saves require the fingerprint pipeline "
                "(CheckpointManager(fingerprint=True))")
        self.mgr = mgr
        self.spread_steps = max(1, int(spread_steps))
        self.predictor = DirtyPredictor(margin=margin)
        self.interpret = interpret
        self.arena = StagingArena(slots=staging_slots)
        self._event: Optional[_Event] = None
        self.last_manifest: Optional[Manifest] = None
        self.last_snapshot_fps: Dict[str, List[LeafFP]] = {}

    # ------------------------------------------------------------- begin
    def begin(self, state: Dict[str, PyTree], step: int, *,
              meta: Optional[Dict] = None,
              drift_scores: Optional[Dict[str, float]] = None,
              units: Optional[Sequence[str]] = None,
              durability_barrier: Optional[bool] = None) -> None:
        """Open an event for ``step``: dispatch every device read of
        ``state`` and make every content decision.  When ``begin``
        returns, the caller may donate/overwrite the state; the event
        needs only its own staged buffers."""
        if self._event is not None:
            self.finish()
        trace = tracing.Event()
        with tracing.active(trace), tracing.span("ckpt.save.begin"):
            self._event = self._begin(trace, state, int(step), meta=meta,
                                      drift_scores=drift_scores,
                                      units=units,
                                      durability_barrier=durability_barrier)

    def _begin(self, trace: tracing.Event, state: Dict[str, PyTree],
               step: int, *, meta, drift_scores, units,
               durability_barrier) -> _Event:
        mgr = self.mgr
        pool = mgr.transfer_pool
        workers0 = (pool.dispatch.stats() if pool is not None else None)
        mgr.store.reset_stats()
        event_index = mgr.reserve_event_index()
        ctx = PolicyContext(event_index=event_index, step=step,
                            drift_scores=drift_scores)
        prev = _usable_prev(mgr.manifests.load())
        if prev is None:
            selected = mgr.policy.all_units()
        elif units is not None:
            selected = list(dict.fromkeys(units))
        else:
            selected = list(dict.fromkeys(mgr.policy.select(ctx)))
        entries: Dict[str, Dict[str, Any]] = (
            {u: dict(k) for u, k in prev.entries.items()} if prev else {})

        ev = _Event(step=step, event_index=event_index,
                    prev_step=prev.step if prev else None,
                    entries=entries, selected=selected, meta=meta,
                    durability_barrier=durability_barrier, queue=[],
                    per_slice=1, trace=trace, workers0=workers0)
        for name in selected:
            drift = (drift_scores or {}).get(name)
            for kind in ("weights", "opt"):
                pref = mgr._prev_entry(prev, name, kind)
                self._begin_unit(ev, name, kind, state, pref, drift)
        # Batch-resolve the deferred store-wide dedup probes: one
        # concurrent ``store.has`` per still-queued unit (see
        # ``_begin_unit``).  Same decision, same order of authority —
        # only the round trips overlap each other instead of stacking.
        if ev.queue:
            if pool is not None:
                probes = [(u, pool.submit("probe", mgr.store.has, u.digest))
                          for u in ev.queue]
                hits = [(u, p.result()) for u, p in probes]
            else:
                hits = [(u, mgr.store.has(u.digest)) for u in ev.queue]
            for u, hit in hits:
                if hit:
                    ev.resolved[(u.name, u.kind)] = mgr.store.note_dedup(
                        ev.step, u.name, u.kind, u.digest, prev_ref=u.pref,
                        logical_bytes=u.logical)
                    ev.queue.remove(u)
                    for leaf in u.leaves:
                        leaf.dev = None
        ev.per_slice = max(1, -(-len(ev.queue) // self.spread_steps))
        # Everything is dispatched and every decision is made; nothing
        # has been written, no manifest moved — the canonical "died with
        # a whole event in flight" drill.
        faults.crash_point("snapshot_overlap")
        return ev

    def _begin_unit(self, ev: _Event, name: str, kind: str,
                    state: Dict[str, PyTree], pref,
                    drift: Optional[float]) -> None:
        mgr = self.mgr
        bb = mgr.fp_block_bytes
        attrs = {"unit": name, "kind": kind}
        with tracing.span("ckpt.save.fingerprint", **attrs):
            tree = mgr._extract(state, name, kind)
            flat = flatten_with_paths(tree)
            arrs = [jnp.asarray(a) for _, a in flat]
            metas = fputil.meta_table(tree, bb)
            nb_total = sum(m.n_blocks for m in metas)
            ev.blocks_total += nb_total

            # Delta base planned from structure alone (meta_matches never
            # reads checksums) so the fused kernel can compare against it
            # in the same pass that fingerprints.
            base_digest, base_tbl = mgr._delta_base(name, kind, pref, metas)
            results = None
            if base_tbl is not None:
                caps = [self.predictor.predict(name, kind, m.path,
                                               m.n_blocks, drift)
                        for m in metas]
                results = bgather.gather_tree_dirty(
                    arrs, [np.asarray(b.fp) for b in base_tbl], caps,
                    block_bytes=bb, interpret=self.interpret)
                cur = [LeafFP(path=m.path, shape=m.shape, dtype=m.dtype,
                              nbytes=m.nbytes, block_bytes=bb,
                              fp=r.fp, sumsq=r.sumsq)
                       for m, r in zip(metas, results)]
            else:
                cur = bfp.fingerprint_tree(tree, block_bytes=bb,
                                           interpret=self.interpret)
            path = bfp.kernel_path(self.interpret)
            ev.kernel_leaves[f"fp_leaves_{path}"] += len(arrs)
            if results is not None:
                ev.kernel_leaves[f"gather_leaves_{path}"] += len(arrs)
            faults.crash_point("fingerprint")

            # The fingerprint tables are ~0.02% of the data: fetching
            # them synchronously is what every decision below hangs off.
            host = tables_to_host(cur)
            tblob = fputil.pack_table(host)
            digest = fputil.fp_digest(tblob)
            logical = sum(l.nbytes for l in host)
            ev.new_fps[(name, kind)] = host
            if kind == "weights":
                ev.snapshot_fps[name] = host

            # Decision order — byte-for-byte the sync ``_save_unit_fp``
            # tree.
            ref_fp = mgr._fp_refs.get((name, kind))
            if ref_fp is None and pref is not None and pref.digest:
                ref_fp = mgr.store.load_fp_table(pref.digest)
            if (ref_fp is not None and pref is not None and pref.digest
                    and bfp.leaves_match(host, ref_fp)):
                # Unchanged: a predicted-dirty gather (if any) is
                # discarded on device — the clean-misprediction that costs
                # nothing.
                ev.resolved[(name, kind)] = mgr.store.note_dedup(
                    ev.step, name, kind, pref.digest, prev_ref=pref,
                    logical_bytes=logical)
                for m in metas:
                    self.predictor.observe(name, kind, m.path, 0)
                return
        # The store-wide dedup probe (``store.has``) is deferred: the
        # unit stages eagerly and ``begin`` batch-resolves every probe
        # concurrently through the transfer pool — against a remote
        # backend each probe is a full-latency round trip, and paying
        # them serially would put n_units x RTT on the stall path.  A
        # probe hit just un-queues the unit (decision unchanged; the
        # staged copies are discarded — a dedup-misprediction that
        # costs device copies, never correctness).

        # The dirty counts and indices are fetched here; the payload
        # copies are only started (``copy_to_host_async``), and ticks
        # wait for them in ``ckpt.save.pack``.
        with tracing.span("ckpt.save.d2h", **attrs):
            use_delta = base_tbl is not None
            counts: List[int] = []
            if use_delta:
                counts = [int(c) for c in jax.device_get(
                    [r.count for r in results])]
                tracing.count("d2h_calls")
                if sum(counts) > mgr.fp_max_dirty_frac * nb_total:
                    use_delta = False

            leaves: List[_StagedLeaf] = []
            if use_delta:
                for i, (m, r, c) in enumerate(zip(metas, results, counts)):
                    if c > r.capacity:
                        # Under-prediction: the count is authoritative,
                        # the buffers are live — re-gather at the true
                        # size before the state is donated.
                        ev.overflows += 1
                        self.predictor.overflows += 1
                        r = bgather.gather_dirty(
                            arrs[i], np.asarray(base_tbl[i].fp), capacity=c,
                            block_bytes=bb, interpret=self.interpret)
                        results[i] = r
                    else:
                        self.predictor.hits += 1
                    self.predictor.observe(name, kind, m.path, c)
                idxs = jax.device_get([r.idx for r in results])
                tracing.count("d2h_calls")
                for m, r, c, idx in zip(metas, results, counts, idxs):
                    dev = r.blocks
                    if c:
                        # start the D2H now; ticks only collect it
                        try:
                            dev.copy_to_host_async()
                        except AttributeError:  # pragma: no cover - np
                            pass
                    leaves.append(_StagedLeaf(meta=m, mode="delta", dev=dev,
                                              idx=np.asarray(idx[:c]),
                                              count=c))
            else:
                copies = _device_copy(arrs)
                for dev in copies:
                    try:
                        dev.copy_to_host_async()
                    except AttributeError:  # pragma: no cover - np input
                        pass
                for m, dev in zip(metas, copies):
                    leaves.append(_StagedLeaf(meta=m, mode="full", dev=dev))
                for m in metas:
                    self.predictor.observe(name, kind, m.path, m.n_blocks)
        ev.queue.append(_StagedUnit(
            name=name, kind=kind, pref=pref, digest=digest, tblob=tblob,
            logical=logical, nb_total=nb_total, full=not use_delta,
            base_digest=base_digest if use_delta else None, leaves=leaves))

    # -------------------------------------------------------------- tick
    def tick(self) -> Optional[Manifest]:
        """Advance one spread slice; returns the manifest on the tick
        that completes (and commits) the event, else None.

        The commit deliberately happens on the tick AFTER the one that
        staged the last slice: that buys the final slice's writes a full
        compute step to drain in the background, so the commit-time
        drain — the only blocking wait left — is usually empty."""
        ev = self._event
        if ev is None:
            return None
        faults.crash_point("spread_slice")
        with tracing.active(ev.trace):
            if ev.queue:
                with tracing.span("ckpt.save.slice"):
                    for _ in range(min(ev.per_slice, len(ev.queue))):
                        self._stage_and_submit(ev, ev.queue.pop(0))
                ev.slices += 1
                return None
            return self._commit(ev)

    def finish(self) -> Optional[Manifest]:
        """Run the event to completion NOW (sync point: preemption saves,
        shutdown, or a new event beginning mid-spread)."""
        ev = self._event
        if ev is None:
            return None
        with tracing.active(ev.trace):
            with tracing.span("ckpt.save.slice"):
                while ev.queue:
                    faults.crash_point("spread_slice")
                    self._stage_and_submit(ev, ev.queue.pop(0))
            ev.slices += 1
            return self._commit(ev)

    @property
    def active(self) -> bool:
        return self._event is not None

    def _stage_and_submit(self, ev: _Event, unit: _StagedUnit) -> None:
        """Wait for the unit's device-to-host copies, pack them into a
        staging slot and hand the packet to a writer lane: the
        ``ckpt.save.pack`` span."""
        with tracing.span("ckpt.save.pack", unit=unit.name, kind=unit.kind):
            self._stage_unit(ev, unit)

    def _stage_unit(self, ev: _Event, unit: _StagedUnit) -> None:
        mgr = self.mgr
        total = 0
        for leaf in unit.leaves:
            if leaf.mode == "delta":
                total += leaf.count * leaf.meta.block_bytes
            else:
                total += leaf.meta.nbytes
        slot = self.arena.acquire(total)
        try:
            payloads: List[fputil.LeafPayload] = []
            for leaf in unit.leaves:
                m = leaf.meta
                if leaf.mode == "delta":
                    data: Any = b""
                    if leaf.count:
                        arr = np.asarray(leaf.dev)[:leaf.count]
                        tracing.count("d2h_calls")
                        data = slot.pack(_byte_view(arr))
                        ev.d2h_bytes += data.nbytes
                        ev.blocks_moved += leaf.count
                    payloads.append(fputil.LeafPayload(
                        path=m.path, shape=m.shape, dtype=m.dtype,
                        nbytes=m.nbytes, block_bytes=m.block_bytes,
                        idx=leaf.idx, data=data))
                else:
                    arr = np.asarray(leaf.dev)
                    tracing.count("d2h_calls")
                    data = slot.pack(_byte_view(arr))
                    ev.d2h_bytes += data.nbytes
                    ev.blocks_moved += m.n_blocks
                    payloads.append(fputil.LeafPayload(
                        path=m.path, shape=m.shape, dtype=m.dtype,
                        nbytes=m.nbytes, block_bytes=m.block_bytes,
                        idx=None, data=data))
                leaf.dev = None  # device buffer no longer needed
            ev.staged_bytes += total
            packet = fputil.FingerprintPacket(
                digest=unit.digest, table=unit.tblob, leaves=payloads,
                full=unit.full, base_digest=unit.base_digest,
                logical_bytes=unit.logical)
            faults.crash_point("gather")
        except BaseException:
            self.arena.release(slot)
            raise
        key = (unit.name, unit.kind)
        if mgr.writer is not None:
            ev.pending[key] = mgr.writer.submit(
                self._write_and_release, ev.step, unit, packet, slot)
        else:
            ev.resolved[key] = self._write_and_release(
                ev.step, unit, packet, slot)

    def _write_and_release(self, step: int, unit: _StagedUnit, packet,
                           slot):
        """Runs on a writer thread: materialize the staged views into
        private bytes first, then recycle the slot, THEN do the (slow)
        store write — so a high-latency backend never holds a staging
        slot hostage and the training thread's next stage can reuse it."""
        try:
            for l in packet.leaves:
                if not isinstance(l.data, bytes):
                    l.data = bytes(l.data)
        except BaseException:
            # Drop every view into the slot even on failure: a live
            # memoryview pins the shm mapping and would make a later
            # grow-in-place fail to close the segment.
            for l in packet.leaves:
                if not isinstance(l.data, bytes):
                    l.data = b""
            raise
        finally:
            self.arena.release(slot)
        return self.mgr.store.write_fp(step, unit.name, unit.kind,
                                       packet, prev_ref=unit.pref)

    # ------------------------------------------------------------ commit
    def _commit(self, ev: _Event) -> Manifest:
        """Drain, commit, account: the ``ckpt.save`` span of the call that
        completes the event.  The event's stall is that span plus its
        ``ckpt.save.begin`` and ``ckpt.save.slice`` spans."""
        mgr = self.mgr
        with tracing.span("ckpt.save"):
            with tracing.span("ckpt.save.drain"):
                if mgr.writer is not None:
                    mgr.writer.drain()
                    for key, p in ev.pending.items():
                        ev.resolved[key] = p.result()

            latest = mgr.manifests.load()
            latest_step = latest.step if latest is not None else None
            if latest_step != ev.prev_step:
                # A direct save committed mid-event (callers should
                # finish() first).  The event's own objects are
                # content-addressed and final; only the carried-forward
                # entries must re-anchor.
                log.warning(
                    "manifest for step %s committed while overlapped event "
                    "for step %s was in flight; re-anchoring carried "
                    "entries", latest_step, ev.step)
                lat = _usable_prev(latest)
                base_entries = ({u: dict(k) for u, k in lat.entries.items()}
                                if lat else {})
            else:
                base_entries = ev.entries
            for (name, kind), ref in ev.resolved.items():
                base_entries.setdefault(name, {})[kind] = ref
            manifest, storage = mgr._commit_event(
                step=ev.step, entries=base_entries, selected=ev.selected,
                meta=ev.meta, new_fps=ev.new_fps,
                event_index=ev.event_index,
                durability_barrier=ev.durability_barrier)
        total = ev.trace.seconds()
        stages, counters = ev.trace.fold()
        stats = mgr._event_stats(
            step=ev.step, selected=ev.selected, d2h_bytes=ev.d2h_bytes,
            blocks_moved=ev.blocks_moved, blocks_total=ev.blocks_total,
            storage=storage, workers0=ev.workers0,
            kernel_leaves=ev.kernel_leaves, stages=stages,
            counters=counters,
            timings={"snapshot_seconds": stages["ckpt.save.begin"],
                     "stage_seconds": stages.get("ckpt.save.slice", 0.0),
                     "writeback_seconds": stages["ckpt.save.drain"],
                     "stall_seconds": (stages["ckpt.save.begin"]
                                       + stages.get("ckpt.save.slice", 0.0)
                                       + stages["ckpt.save"]),
                     "total_seconds": total})
        stats["save_mode"] = "overlapped"
        stats["spread_steps"] = self.spread_steps
        stats["spread_slices"] = ev.slices
        stats["staged_bytes"] = ev.staged_bytes
        stats["overflow_redispatches"] = ev.overflows
        mgr.last_save_stats = stats
        self.last_manifest = manifest
        self.last_snapshot_fps = ev.snapshot_fps
        self._event = None
        return manifest

    def abort(self) -> None:
        """Drop an in-flight event without committing (error paths in
        tests; a real crash needs no cleanup — that is the point).  Any
        already-written objects are unreferenced and will be GC-swept."""
        ev, self._event = self._event, None
        if ev is None:
            return
        if self.mgr.writer is not None:
            try:
                self.mgr.writer.drain()
            except Exception:  # noqa: BLE001 - writes may have crashed
                pass

    def close(self) -> None:
        self.abort()
        self.arena.close()

"""Shard-native checkpointing: per-participant sharded save, slice-aware
restore, and the two-phase manifest commit barrier (docs/storage.md).

The classic save path gathers every selected unit as a *global* array
onto one host and writes one object per (unit, kind) — a single-writer
bottleneck at multi-host scale.  This module re-layers the pipeline so
the unit of IO is a **shard object**: ``(unit, kind, shard_spec)`` where
the spec records the global shape plus the index blocks the object
covers.  Everything below the manifest is unchanged — a shard object is
an ordinary content-addressed chunk, so dedup, XOR/BD02 deltas, the
device-side fingerprint compare, tiered spill, refcounted GC, and the
merge engine all operate per shard object.

Roles:

- :class:`ShardedSaver` — one per *participant* (a partition of the save
  job: one JAX process in production, a virtual thread/subprocess in
  tests).  Each participant fingerprints/gathers ONLY its owned index
  blocks of every selected unit, writes its shard objects through the
  shared dedup/delta/tiered machinery, drains its writes durable, and
  *publishes* a per-participant completion record under
  ``root/shards/step-<N>/`` (phase one of the commit).
- :class:`ShardCoordinator` — phase two: once every participant's record
  is present it validates that each selected unit's combined shard set
  exactly tiles the unit's global arrays and that every object (and
  delta base) is durable, then commits ``manifest-<step>.json`` through
  the ordinary atomic manifest protocol.  A crash anywhere before that
  commit leaves the previous manifest authoritative — the published
  records and orphaned shard objects are swept by the next GC.
- :class:`ShardedCheckpointer` — single-process convenience that runs N
  virtual participants as threads over one shared
  :class:`CheckpointManager` and commits, exposing the familiar
  ``save()``/``restore()`` surface (the trainer's ``--shard-participants``
  path, and how CI exercises the barrier without real multi-host JAX).

Owned slices come from :func:`participant_wanted`: either the target
``NamedSharding``'s device->index map restricted to the participant's
devices (replicated blocks are assigned to exactly one owner, so the
union over participants is always an exact disjoint cover), or — with no
mesh — a deterministic contiguous axis-0 split.  The same callable
drives the restore side: ``plan_restore(..., owned=...)`` schedules only
the shard objects whose blocks intersect the participant's slices, so a
save-on-MxN checkpoint restores on PxQ reading strictly fewer bytes than
a full-array restore whenever the shardings overlap partially.
"""
from __future__ import annotations

import contextvars
import dataclasses
import logging
import shutil
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.checkpoint import faults, tracing
from repro.checkpoint.async_io import PendingResult
from repro.checkpoint.backends.localfs import atomic_write
from repro.checkpoint.chunk_store import ChunkRef
from repro.checkpoint.fingerprint import KERNEL_LEAF_KEYS
from repro.checkpoint.serial import (
    flatten_with_paths,
    shard_leaf_key,
    unflatten_from_paths,
)
from repro.core import jsonutil
from repro.core.layer_registry import OPT_KINDS
from repro.core.manifest import Manifest, entry_refs, is_sharded
from repro.core.policies import PolicyContext
from repro.optim.groups import get_at
from repro.parallel import sharding as shd

log = logging.getLogger("repro.checkpoint.sharded")

PyTree = Any
RECORD_VERSION = 1

# wanted(unit, kind, leaf_path, global_shape) -> index blocks this
# participant owns (() = nothing), or None meaning "everything" (the
# non-sharded caller).
WantedFn = Callable[[str, str, str, Tuple[int, ...]],
                    Optional[Tuple[shd.Block, ...]]]


class ShardBarrierError(RuntimeError):
    """The two-phase commit cannot proceed (missing/incomplete/
    inconsistent participant records, or a non-durable shard object)."""


# ---------------------------------------------------------------------------
# ShardSpec: the JSON blob a manifest ref carries for a shard object
# ---------------------------------------------------------------------------

def _blk(b) -> shd.Block:
    """Normalize a JSON-roundtripped block (lists) to the tuple form the
    block math in repro.parallel.sharding operates on."""
    return tuple((int(s), int(e)) for s, e in b)


def spec_leaves(spec: Dict[str, Any]) -> List[Dict[str, Any]]:
    return list(spec.get("leaves", ()))


def leaf_blocks(leaf: Dict[str, Any]) -> Tuple[shd.Block, ...]:
    return tuple(_blk(b) for b in leaf["blocks"])


def spec_key(spec: Dict[str, Any]) -> Tuple:
    """Hashable identity of a shard layout (leaf paths + shapes +
    blocks), independent of JSON list/tuple representation and of the
    participant id — how a shard finds its previous incarnation (delta
    base) and its older-manifest fallback candidates."""
    return tuple(sorted(
        (leaf["path"], tuple(int(d) for d in leaf["shape"]),
         str(leaf["dtype"]), leaf_blocks(leaf))
        for leaf in spec_leaves(spec)))


def spec_overlaps(spec: Dict[str, Any], wanted: WantedFn,
                  unit: str, kind: str) -> bool:
    """Does any block of this shard object intersect the caller's owned
    slices?  Drives plan-time shard skipping."""
    for leaf in spec_leaves(spec):
        shape = tuple(int(d) for d in leaf["shape"])
        want = wanted(unit, kind, leaf["path"], shape)
        if want is None:
            return True
        for blk in leaf_blocks(leaf):
            for w in want:
                if blk == w or (len(blk) == len(w)
                                and shd.intersect_blocks(blk, w)):
                    return True
    return False


def assemble_shards(parts: Sequence[Tuple[Dict[str, Any], PyTree]],
                    *, partial: bool) -> PyTree:
    """Rebuild a unit's (sub)tree from decoded shard objects.

    Each element is ``(spec, tree)`` — the manifest's ShardSpec and the
    decoded shard payload (block arrays keyed by ``path#b<i>``).  Leaves
    are assembled into per-path host buffers sized from the spec's global
    shapes; ``partial=True`` (an owned-filtered restore that skipped
    shards) zero-fills so uncovered regions restore as zeros, matching
    the engine's unit-filter semantics."""
    bufs: Dict[str, np.ndarray] = {}
    alloc = np.zeros if partial else np.empty
    for spec, tree in parts:
        flat = dict(flatten_with_paths(tree))
        for leaf in spec_leaves(spec):
            path = leaf["path"]
            shape = tuple(int(d) for d in leaf["shape"])
            buf = bufs.get(path)
            if buf is None:
                buf = bufs[path] = alloc(shape, np.dtype(str(leaf["dtype"])))
            for i, blk in enumerate(leaf_blocks(leaf)):
                piece = np.asarray(flat[shard_leaf_key(path, i)])
                buf[shd.block_slices(blk)] = piece.reshape(
                    tuple(e - s for s, e in blk) or piece.shape)
    return unflatten_from_paths(dict(bufs))


# ---------------------------------------------------------------------------
# Owned-slice resolution
# ---------------------------------------------------------------------------

def _slice_leading_axis(s):
    """Sharding of one stacked layer's slice: drop the leading (layers)
    dim's spec entry."""
    from jax.sharding import NamedSharding, PartitionSpec
    spec = tuple(s.spec)
    if not spec:
        return s
    return NamedSharding(s.mesh, PartitionSpec(*spec[1:]))


def participant_wanted(registry, participant_id: int, n_participants: int,
                       *, shardings: Optional[Dict[str, PyTree]] = None
                       ) -> WantedFn:
    """The owned-slice resolver for one participant.

    With ``shardings`` (a state-shardings tree as from
    ``launch.steps.state_shardings``): the participant owns the index
    blocks of the devices in its contiguous 1/N cut of the mesh device
    list, with each replicated block assigned to exactly one owner —
    union over participants is an exact disjoint cover of every leaf.
    Without: a deterministic contiguous axis-0 split
    (:func:`repro.parallel.sharding.uniform_blocks`), the mesh-free
    virtual-participant mode."""
    if not (0 <= participant_id < n_participants):
        raise ValueError(
            f"participant {participant_id} outside 0..{n_participants - 1}")
    if shardings is None:
        def wanted(unit: str, kind: str, path: str,
                   shape: Tuple[int, ...]) -> Tuple[shd.Block, ...]:
            return shd.uniform_blocks(shape, participant_id, n_participants)
        return wanted

    leaf_cache: Dict[Tuple[str, str], Dict[str, Any]] = {}
    parts_cache: Dict[Any, list] = {}

    def parts_for(mesh):
        parts = parts_cache.get(mesh)
        if parts is None:
            parts = parts_cache[mesh] = shd.partition_devices(
                list(mesh.devices.flat), n_participants)
        return parts

    def leaves_for(unit: str, kind: str) -> Dict[str, Any]:
        cached = leaf_cache.get((unit, kind))
        if cached is not None:
            return cached
        u = registry.by_name[unit]
        if kind == "weights":
            sub = get_at(shardings["params"], u.path)
        else:
            sub = {k: get_at(shardings["opt"][k], u.path)
                   for k in OPT_KINDS}
        if u.index is not None:
            sub = jax.tree.map(_slice_leading_axis, sub)
        out = dict(flatten_with_paths(sub))
        leaf_cache[(unit, kind)] = out
        return out

    def wanted(unit: str, kind: str, path: str,
               shape: Tuple[int, ...]) -> Tuple[shd.Block, ...]:
        s = leaves_for(unit, kind).get(path)
        if s is None:
            return shd.uniform_blocks(shape, participant_id, n_participants)
        blocks = shd.partition_leaf_blocks(s, shape, parts_for(s.mesh))
        return blocks[participant_id]

    return wanted


def unit_leaf_shapes(registry, unit: str, kind: str,
                     shapes: Optional[PyTree] = None) -> Dict[str, Tuple]:
    """leaf path -> global shape for one (unit, kind), derived from the
    model's parameter shapes (no state materialization) — the
    coordinator's completeness oracle.  Pass ``shapes``
    (``model.param_shapes()``) when calling per unit: it is an
    ``eval_shape`` trace, so recomputing it per call is wasteful."""
    u = registry.by_name[unit]
    if shapes is None:
        shapes = registry.model.param_shapes()
    sub = get_at(shapes, u.path)
    if u.index is not None:
        sub = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(tuple(s.shape[1:]), s.dtype), sub)
    if kind == "opt":
        sub = {k: sub for k in OPT_KINDS}
    return {path: tuple(int(d) for d in leaf.shape)
            for path, leaf in flatten_with_paths(sub)}


# ---------------------------------------------------------------------------
# Participant save
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ParticipantResult:
    participant_id: int
    step: int
    record_path: Path
    # (unit, kind) -> shard ChunkRef (spec attached) for units this
    # participant owns a piece of
    refs: Dict[Tuple[str, str], ChunkRef]
    stats: Dict[str, Any]
    # fingerprint reference vectors to advance AFTER the coordinator
    # commits (same commit-then-advance rule as CheckpointManager.save)
    new_fps: Dict[Tuple[str, str], Any]


def record_dir(root: Path | str, step: int) -> Path:
    return Path(root) / "shards" / f"step-{int(step):08d}"


def _record_path(root: Path | str, step: int, pid: int) -> Path:
    return record_dir(root, step) / f"participant-{pid:04d}.json"


# save_shards sentinel: "load the newest manifest yourself" (None is a
# legitimate value meaning "no previous manifest").
_LOAD_PREV = object()


def _usable_prev(prev: Optional[Manifest]) -> Optional[Manifest]:
    """Same guard as CheckpointManager.save: a pre-content-addressing
    manifest (digest-less refs) cannot be carried forward — the store
    only reads by digest — so the event must start a fresh full base
    rather than commit unrestorable entries."""
    if prev is None:
        return None
    if any(not r.digest for kinds in prev.entries.values()
           for e in kinds.values() for r in entry_refs(e)):
        log.warning("previous manifest at step %s predates content "
                    "addressing; forcing a full sharded save", prev.step)
        return None
    return prev


class ShardedSaver:
    """One save participant: gathers/fingerprints only its owned slices,
    writes shard objects through the manager's store/writer, and
    publishes a completion record (phase one of the two-phase commit).

    ``manager`` may be shared between participants (virtual threads) or
    private per process (each process opens its own
    :class:`CheckpointManager` on the same root; content-addressed
    writes are atomic and idempotent, so concurrent cross-process
    writers at worst duplicate work, never corrupt).  The saver never
    commits manifests, never advances fingerprint refs, and never runs
    GC — those are the coordinator's (phase two)."""

    def __init__(self, manager, participant_id: int, n_participants: int,
                 *, shardings: Optional[Dict[str, PyTree]] = None):
        self.mgr = manager
        self.participant_id = int(participant_id)
        self.n_participants = int(n_participants)
        self.wanted: WantedFn = participant_wanted(
            manager.registry, self.participant_id, self.n_participants,
            shardings=shardings)

    # ------------------------------------------------------------- internals
    def _store_key(self, unit: str) -> str:
        """Per-participant unit key for the store's delta-run/rebase
        accounting (shards of one unit drift independently per
        participant)."""
        return f"{unit}@p{self.participant_id}"

    def _prev_shard_ref(self, prev: Optional[Manifest], unit: str,
                        kind: str, spec: Dict[str, Any]
                        ) -> Optional[ChunkRef]:
        """The unit's previous shard object with the SAME layout — the
        dedup/delta anchor.  A previous global entry (or a different
        shard layout after re-partitioning) can't anchor a block delta,
        so the shard starts a fresh full base."""
        if prev is None:
            return None
        entry = prev.entries.get(unit, {}).get(kind)
        if entry is None or not is_sharded(entry):
            return None
        key = spec_key(spec)
        for ref in entry_refs(entry):
            if ref.spec is not None and spec_key(ref.spec) == key:
                return ref
        return None

    @staticmethod
    def _addressable_pieces(arr, shape) -> Dict[shd.Block, Any]:
        """block -> device-LOCAL piece for a jax.Array, keyed by each
        addressable shard's index rectangle.  This is how a participant
        reads its owned slices without any cross-device computation: when
        the owned blocks come from the same NamedSharding the state lives
        on, every block is a shard already resident on one of the
        participant's devices.  (Global indexing ``arr[slices]`` would
        lower to an all-gather — concurrent participants would interleave
        collectives and deadlock the rendezvous.)"""
        if not hasattr(arr, "addressable_shards"):
            return {}
        try:
            shards = list(arr.addressable_shards)
        except Exception:  # noqa: BLE001 - non-jax array-likes
            return {}
        out: Dict[shd.Block, Any] = {}
        for s in shards:
            out.setdefault(shd.normalize_index(s.index, shape), s.data)
        return out

    def _shard_of(self, unit: str, kind: str, tree: PyTree
                  ) -> Tuple[Optional[Dict[str, Any]], Dict[str, PyTree]]:
        """(spec, shard_tree) of this participant's owned slices of one
        (unit, kind).  Blocks matching an addressable device shard are
        taken device-local; anything else (mesh-free uniform split of a
        host/single-device array) falls back to plain slicing.  Either
        way the pieces stay on device — the fingerprint path hashes them
        there and gathers only dirty blocks."""
        leaves: List[Dict[str, Any]] = []
        shard_tree: Dict[str, Any] = {}
        for path, arr in flatten_with_paths(tree):
            shape = tuple(int(d) for d in np.shape(arr))
            blocks = self.wanted(unit, kind, path, shape)
            if not blocks:
                continue
            pieces = self._addressable_pieces(arr, shape)
            for i, blk in enumerate(blocks):
                piece = pieces.get(blk)
                if piece is None:
                    piece = arr[shd.block_slices(blk)] if blk else arr
                shard_tree[shard_leaf_key(path, i)] = piece
            leaves.append({"path": path, "shape": list(shape),
                           "dtype": str(arr.dtype),
                           "blocks": [list(map(list, b)) for b in blocks]})
        if not leaves:
            return None, {}
        return {"participant": self.participant_id, "leaves": leaves}, \
            shard_tree

    # ------------------------------------------------------------------ save
    def save_shards(self, state: Dict[str, PyTree], *,
                    step: Optional[int] = None,
                    meta: Optional[Dict] = None,
                    drift_scores: Optional[Dict[str, float]] = None,
                    prev: Any = _LOAD_PREV,
                    units: Optional[Sequence[str]] = None,
                    durability_barrier: bool = True) -> ParticipantResult:
        """Write this participant's shard objects for one save event and
        publish its completion record.  Returns only after every owned
        object is durable on the store's durable tier (writer drained +
        spill drained) — publishing IS the durability claim the
        coordinator trusts.

        ``prev`` lets a single-process orchestrator
        (:class:`ShardedCheckpointer`) load + parse the newest manifest
        once and share it, instead of N parses per event; omitted, the
        participant loads it itself (the multi-process mode).

        ``units`` overrides the policy selection (every participant of
        one event must pass the SAME list — the barrier checks
        agreement); ``durability_barrier=False`` skips the pre-publish
        spill drain, publishing as soon as objects are on the fast tier —
        the supervisor's preemption hot save (the manifest then records
        ``durable_on="hot"``; see docs/resiliency.md)."""
        mgr = self.mgr
        step = int(state["step"]) if step is None else int(step)
        if prev is _LOAD_PREV:
            prev = mgr.manifests.load()
        prev = _usable_prev(prev)
        # Anchor on the committed chain, not this process's counter:
        # every participant (thread or separate process) must derive the
        # SAME index for the barrier's selection-agreement check.
        # len(all_steps()) would saturate at the retention cap `keep`
        # and freeze event-alternating policies on one half.
        if prev is not None and "event_index" in prev.meta:
            event_index = int(prev.meta["event_index"]) + 1
        else:
            event_index = len(mgr.manifests.all_steps())
        ctx = PolicyContext(event_index=event_index, step=step,
                            drift_scores=drift_scores)
        if prev is None:
            selected = mgr.policy.all_units()
        elif units is not None:
            selected = list(dict.fromkeys(units))
        else:
            selected = list(dict.fromkeys(mgr.policy.select(ctx)))

        d2h_bytes = 0
        blocks_moved = 0
        blocks_total = 0
        kernel_leaves: Counter = Counter()
        pending: Dict[Tuple[str, str], PendingResult] = {}
        refs: Dict[Tuple[str, str], ChunkRef] = {}
        specs: Dict[Tuple[str, str], Dict[str, Any]] = {}
        new_fps: Dict[Tuple[str, str], Any] = {}
        for name in selected:
            for kind in ("weights", "opt"):
                spec, shard_tree = self._shard_of(
                    name, kind, mgr._extract(state, name, kind))
                if spec is None:
                    continue  # this participant owns nothing of the unit
                specs[(name, kind)] = spec
                pref = self._prev_shard_ref(prev, name, kind, spec)
                ukey = self._store_key(name)
                if not mgr.fingerprint:
                    with tracing.span("ckpt.save.d2h", unit=name, kind=kind):
                        host = jax.device_get(shard_tree)
                        tracing.count("d2h_calls")
                    d2h_bytes += sum(np.asarray(x).nbytes
                                     for x in jax.tree.leaves(host))
                    if mgr.writer is not None:
                        pending[(name, kind)] = mgr.writer.submit(
                            mgr.store.write, step, ukey, kind, host,
                            prev_ref=pref)
                    else:
                        refs[(name, kind)] = mgr.store.write(
                            step, ukey, kind, host, prev_ref=pref)
                    continue
                res, ustat, cur = mgr._save_unit_fp(
                    step, ukey, kind, lambda: shard_tree, pref)
                d2h_bytes += ustat["d2h_bytes"]
                blocks_moved += ustat["blocks_moved"]
                blocks_total += ustat["blocks_total"]
                kernel_leaves.update(ustat["kernel_leaves"])
                new_fps[(ukey, kind)] = cur
                if isinstance(res, PendingResult):
                    pending[(name, kind)] = res
                else:
                    refs[(name, kind)] = res

        with tracing.span("ckpt.save.drain"):
            for key, p in pending.items():
                refs[key] = p.result()
        # Durability before publish: the record is the participant's
        # claim that its whole shard set survives a process loss.  The
        # preemption hot save waives it — objects on the fast tier are
        # enough to commit against in the seconds before SIGKILL.
        if durability_barrier:
            mgr.store.drain_spill()

        # Attach the spec and restore the clean unit name (the
        # per-participant store key is an internal delta-run namespace).
        for (name, kind), ref in refs.items():
            refs[(name, kind)] = dataclasses.replace(
                ref, unit=name, spec=specs[(name, kind)])

        units: Dict[str, Dict[str, list]] = {}
        for (name, kind), ref in refs.items():
            units.setdefault(name, {})[kind] = [ref.to_json()]
        record = {
            "version": RECORD_VERSION,
            "step": step,
            "participant": self.participant_id,
            "n_participants": self.n_participants,
            "event_index": event_index,
            "policy": mgr.policy.name,
            "saved_units": list(selected),
            "meta": dict(meta or {}),
            "units": units,
            "storage": mgr.store.durability(),
            "complete": True,
        }
        faults.crash_point("participant_record")
        path = _record_path(mgr.root, step, self.participant_id)
        path.parent.mkdir(parents=True, exist_ok=True)
        atomic_write(path, jsonutil.dumps(record, indent=True))
        stats = {
            "participant": self.participant_id,
            "step": step,
            "selected_units": len(selected),
            "shard_objects": len(refs),
            "d2h_bytes": d2h_bytes,
            "blocks_moved": blocks_moved,
            "blocks_total": blocks_total,
            "kernel_leaves": kernel_leaves,
        }
        return ParticipantResult(self.participant_id, step, path, refs,
                                 stats, new_fps)

    def close(self) -> None:
        self.mgr.close()


# ---------------------------------------------------------------------------
# Coordinator (phase two)
# ---------------------------------------------------------------------------

class ShardCoordinator:
    """Collects participant records and performs the manifest commit.

    The commit only happens once (a) every participant's record is
    present and complete, (b) the records agree on the selection, (c)
    every selected unit's combined shard set exactly tiles the unit's
    global arrays, and (d) every referenced object and delta base is
    present in the store.  Any failure raises :class:`ShardBarrierError`
    with the previous manifest untouched — the PR-1 crash rule ("the
    manifest is committed last and only references fully-written
    objects") extended across participants."""

    def __init__(self, manager):
        self.mgr = manager

    def participant_records(self, step: int) -> Dict[int, Dict[str, Any]]:
        out: Dict[int, Dict[str, Any]] = {}
        d = record_dir(self.mgr.root, step)
        if not d.is_dir():
            return out
        for p in sorted(d.glob("participant-*.json")):
            try:
                rec = jsonutil.loads(p.read_bytes())
            except Exception:  # noqa: BLE001 - half-written legacy record
                log.warning("unreadable participant record %s (ignored)", p)
                continue
            if rec.get("complete") and rec.get("version") == RECORD_VERSION:
                out[int(rec["participant"])] = rec
        return out

    def wait_records(self, step: int, n_participants: int,
                     timeout: float = 60.0, poll: float = 0.05
                     ) -> Dict[int, Dict[str, Any]]:
        """Poll for all records (subprocess participants); raises on
        timeout with the missing participant ids."""
        deadline = time.time() + timeout
        while True:
            recs = self.participant_records(step)
            missing = [p for p in range(n_participants) if p not in recs]
            if not missing:
                return recs
            if time.time() >= deadline:
                raise ShardBarrierError(
                    f"step {step}: participants {missing} never published "
                    f"(have {sorted(recs)})")
            time.sleep(poll)

    def _check_cover(self, unit: str, kind: str, refs: Sequence[ChunkRef],
                     model_shapes: PyTree) -> None:
        per_leaf: Dict[str, list] = {}
        shapes: Dict[str, Tuple[int, ...]] = {}
        for ref in refs:
            for leaf in spec_leaves(ref.spec or {}):
                shape = tuple(int(d) for d in leaf["shape"])
                prev = shapes.setdefault(leaf["path"], shape)
                if prev != shape:
                    raise ShardBarrierError(
                        f"{unit}/{kind}: conflicting global shapes for "
                        f"leaf {leaf['path']}: {prev} vs {shape}")
                per_leaf.setdefault(leaf["path"], []).extend(
                    leaf_blocks(leaf))
        expect = unit_leaf_shapes(self.mgr.registry, unit, kind,
                                  shapes=model_shapes)
        for path, shape in expect.items():
            blocks = per_leaf.get(path)
            if not blocks:
                raise ShardBarrierError(
                    f"{unit}/{kind}: no participant covered leaf {path}")
            if shapes[path] != shape:
                raise ShardBarrierError(
                    f"{unit}/{kind}: leaf {path} global shape "
                    f"{shapes[path]} != model shape {shape}")
            if not shd.blocks_cover_exactly(shape, blocks):
                raise ShardBarrierError(
                    f"{unit}/{kind}: shard blocks for leaf {path} do not "
                    f"exactly tile {shape}: {blocks}")
        unknown = set(per_leaf) - set(expect)
        if unknown:
            raise ShardBarrierError(
                f"{unit}/{kind}: shard records cover unknown leaves "
                f"{sorted(unknown)}")

    def commit(self, step: int, n_participants: int, *,
               meta: Optional[Dict] = None,
               check_cover: bool = True) -> Manifest:
        mgr = self.mgr
        # Only this cohort's records count: stale files from a crashed
        # earlier attempt at the SAME step with a different participant
        # count (e.g. 4-wide crash, 2-wide retry — pids 2/3 linger until
        # a successful commit sweeps the dir) must not block the retry.
        records = {pid: rec
                   for pid, rec in self.participant_records(step).items()
                   if (pid < n_participants
                       and int(rec["n_participants"]) == n_participants)}
        missing = [p for p in range(n_participants) if p not in records]
        if missing:
            raise ShardBarrierError(
                f"step {step}: missing participant records {missing} "
                f"(have {sorted(records)}) — previous manifest stays "
                "authoritative")
        first = records[min(records)]
        saved_units = list(first["saved_units"])
        for pid, rec in records.items():
            if list(rec["saved_units"]) != saved_units:
                raise ShardBarrierError(
                    f"step {step}: participant {pid} selected "
                    f"{rec['saved_units']} but participant {min(records)} "
                    f"selected {saved_units} — policies disagree")
            if int(rec["event_index"]) != int(first["event_index"]):
                # Participants that read the manifest chain on opposite
                # sides of an intervening commit would skew every later
                # event-alternating selection.
                raise ShardBarrierError(
                    f"step {step}: participant {pid} derived event_index "
                    f"{rec['event_index']} but participant {min(records)} "
                    f"derived {first['event_index']} — records straddle "
                    "another commit; re-run the participants")

        prev = _usable_prev(mgr.manifests.load())
        entries: Dict[str, Dict[str, Any]] = (
            {u: dict(k) for u, k in prev.entries.items()} if prev else {})
        model_shapes = (mgr.registry.model.param_shapes()
                        if check_cover else None)
        for unit in saved_units:
            for kind in ("weights", "opt"):
                refs: List[ChunkRef] = []
                for pid in sorted(records):
                    for rj in (records[pid]["units"].get(unit, {})
                               .get(kind, [])):
                        refs.append(ChunkRef.from_json(rj))
                if not refs:
                    raise ShardBarrierError(
                        f"step {step}: no participant published shards "
                        f"for selected unit {unit}/{kind}")
                for ref in refs:
                    for d in filter(None, (ref.digest, ref.delta_base)):
                        if not mgr.store.has(d):
                            raise ShardBarrierError(
                                f"step {step}: shard object {d} for "
                                f"{unit}/{kind} is not durable in the "
                                "store — refusing to commit")
                if check_cover:
                    self._check_cover(unit, kind, refs, model_shapes)
                entries[unit] = dict(entries.get(unit, {}))
                entries[unit][kind] = tuple(refs)

        # Every record validated, every object durable: the point of no
        # return is next (the manifest write itself has its own
        # manifest_commit/manifest_latest points inside).
        faults.crash_point("barrier")
        event_index = int(first["event_index"])
        storage = mgr.store.durability()
        manifest = Manifest(
            step=step, entries=entries,
            meta=dict(first.get("meta", {}), **(meta or {}),
                      event_index=event_index, policy=first["policy"],
                      storage=storage,
                      sharded={"n_participants": n_participants}),
            saved_units=saved_units)
        replaced = mgr.manifests.load(step)
        mgr.manifests.commit(manifest)
        mgr.store.incref(manifest.referenced_digests().elements())
        if replaced is not None:
            mgr.store.decref(replaced.referenced_digests().elements())
        mgr._event_index = event_index + 1
        mgr.gc()
        log.info("sharded commit: step %s, %d participants, %d units, "
                 "durable_on=%s", step, n_participants, len(saved_units),
                 storage["durable_on"])
        # This step's records served their purpose; also sweep stale
        # dirs of older crashed events (their orphaned objects were
        # already GC'd above — refcount zero).
        for d in (Path(mgr.root) / "shards").glob("step-*"):
            try:
                if int(d.name.split("-")[1]) <= step:
                    shutil.rmtree(d, ignore_errors=True)
            except (ValueError, IndexError):
                continue
        return manifest


# ---------------------------------------------------------------------------
# Virtual participants (single-process convenience)
# ---------------------------------------------------------------------------

class ShardedCheckpointer:
    """Run N virtual participants (threads) over one shared manager and
    commit — the drop-in ``save()`` the trainer and benchmarks use.

    Thread participants exercise the real code path: per-participant
    slice ownership, per-shard dedup/delta, record publish, barrier
    validation, and the coordinator commit all behave exactly as they
    would across processes; only the store instance is shared (which is
    also what lets RAM-tier backends participate)."""

    def __init__(self, manager, n_participants: int, *,
                 shardings: Optional[Dict[str, PyTree]] = None,
                 parallel: bool = True):
        self.mgr = manager
        self.n_participants = int(n_participants)
        self.savers = [ShardedSaver(manager, pid, self.n_participants,
                                    shardings=shardings)
                       for pid in range(self.n_participants)]
        self.coordinator = ShardCoordinator(manager)
        self.parallel = parallel

    def save(self, state: Dict[str, PyTree], *, step: Optional[int] = None,
             meta: Optional[Dict] = None,
             drift_scores: Optional[Dict[str, float]] = None,
             units: Optional[Sequence[str]] = None,
             durability_barrier: Optional[bool] = None) -> Manifest:
        step = int(state["step"]) if step is None else int(step)
        trace = tracing.Event()
        with tracing.active(trace), tracing.span("ckpt.save"):
            manifest, results = self._save_event(
                state, step, meta=meta, drift_scores=drift_scores,
                units=units, durability_barrier=durability_barrier)
        stages, counters = trace.fold()
        io = dict(self.mgr.store.stats)
        d2h = sum(r.stats["d2h_bytes"] for r in results)
        moved = sum(r.stats["blocks_moved"] for r in results)
        total = sum(r.stats["blocks_total"] for r in results)
        kernel_leaves = sum((r.stats["kernel_leaves"] for r in results),
                            Counter())
        self.mgr.last_save_stats = {
            "step": step,
            "selected_units": len(manifest.saved_units),
            "total_units": len(self.mgr.registry.units),
            "participants": self.n_participants,
            "shard_objects": sum(r.stats["shard_objects"] for r in results),
            "total_seconds": stages["ckpt.save"],
            "stages": stages,
            "d2h_bytes": d2h,
            "d2h_calls": counters["d2h_calls"],
            "hashed_bytes": io["hashed_bytes"],
            "dirty_block_frac": (moved / total if total
                                 else (0.0 if self.mgr.fingerprint else 1.0)),
            "logical_bytes": io["logical_bytes"],
            "written_bytes": io["written_bytes"],
            "dedup_hits": io["dedup_hits"],
            "delta_chunks": io["delta_chunks"],
            "full_chunks": io["full_chunks"],
            "backend": manifest.meta["storage"]["backend"],
            "durable_on": manifest.meta["storage"]["durable_on"],
            "spill_pending": manifest.meta["storage"]["pending_spill"],
            **{k: kernel_leaves.get(k, 0) for k in KERNEL_LEAF_KEYS},
        }
        return manifest

    def _save_event(self, state, step: int, *, meta, drift_scores, units,
                    durability_barrier
                    ) -> Tuple[Manifest, List[ParticipantResult]]:
        """The body of ``save`` inside its ``ckpt.save`` span: every
        participant's shards, then the coordinator's commit."""
        self.mgr.store.reset_stats()
        # One manifest parse for the whole event, shared by every
        # participant (they must agree on it anyway — the barrier checks
        # the derived event_index).
        prev = self.mgr.manifests.load()
        barrier = (True if durability_barrier is None
                   else durability_barrier)

        def run(saver: ShardedSaver) -> ParticipantResult:
            return saver.save_shards(state, step=step, meta=meta,
                                     drift_scores=drift_scores, prev=prev,
                                     units=units,
                                     durability_barrier=barrier)

        if self.parallel and self.n_participants > 1:
            # Each participant runs in a copy of this context, so its
            # spans belong to the event.
            with ThreadPoolExecutor(
                    max_workers=self.n_participants,
                    thread_name_prefix="ckpt-shard") as pool:
                futures = [pool.submit(contextvars.copy_context().run,
                                       run, s) for s in self.savers]
                results = [f.result() for f in futures]
        else:
            results = [run(s) for s in self.savers]
        with tracing.span("ckpt.save.commit"):
            manifest = self.coordinator.commit(step, self.n_participants)
            # Commit is durable: only now may the device-side fingerprint
            # references advance (same rule as CheckpointManager.save).
            for r in results:
                self.mgr._fp_refs.update(r.new_fps)
        return manifest, results

    def __getattr__(self, name: str):
        # restore / restore_meta / drain_spill / close / store /
        # last_save_stats / disk_usage ... all delegate to the manager.
        return getattr(self.mgr, name)


# ---------------------------------------------------------------------------
# Test/bench utilities
# ---------------------------------------------------------------------------

def combine_states(state_like: Dict[str, PyTree], registry,
                   results: Sequence[Dict[str, PyTree]],
                   wanteds: Sequence[WantedFn],
                   parts: Sequence[str] = ("params", "opt")
                   ) -> Dict[str, PyTree]:
    """Stitch per-participant restores back into one global state: each
    participant contributes exactly its owned blocks (its restore is
    only guaranteed correct there).  Host-side; tests and the smoke use
    it to check resharded restores bit-exactly."""
    out: Dict[str, PyTree] = {
        p: jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), state_like[p])
        for p in parts}
    for res, wanted in zip(results, wanteds):
        for name in registry.unit_names():
            u = registry.by_name[name]
            for part in parts:
                kind = "weights" if part == "params" else "opt"
                if part == "params":
                    src = registry.extract_unit(res["params"], name)
                    dst = get_at(out["params"], u.path)
                else:
                    src = registry.extract_opt_unit(res["opt"], name)
                    dst = {k: get_at(out["opt"][k], u.path)
                           for k in OPT_KINDS}
                flat_dst = dict(flatten_with_paths(dst))
                for path, arr in flatten_with_paths(src):
                    shape = tuple(int(d) for d in np.shape(arr))
                    blocks = wanted(name, kind, path, shape)
                    if blocks is None:
                        blocks = (tuple((0, d) for d in shape),)
                    buf = flat_dst[path]
                    a = np.asarray(arr)
                    for blk in blocks:
                        idx = shd.block_slices(blk)
                        if u.index is None:
                            buf[idx] = a[idx]
                        else:
                            buf[(u.index,) + idx] = a[idx]
    if results and "step" in results[0]:
        out["step"] = np.asarray(results[0]["step"])
    return out

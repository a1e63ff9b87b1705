"""Planned, pipelined checkpoint restore (see docs/restore.md).

The seed restore path walked units sequentially: read unit, replay its
delta chain, insert into a zero-filled host tree, and only place data on
device in one bulk ``device_put`` at the very end.  Recovery time there
scales with *everything* — every shared object is re-read per unit, the
full host tree is materialized (and memset) even though every element is
immediately overwritten, and the device sits idle until the last byte is
off disk.

This module replaces that with three separable pieces:

1. **Planner** (``plan_restore``): resolves the manifest chain into a
   deduplicated read plan.  Every distinct object digest appears once no
   matter how many units or delta chains share it, delta bases are
   scheduled as read-once cached dependencies, and the older-manifest
   fallback candidates for every unit are enumerated up front (one pass
   over the manifest list) instead of re-crawled per failing unit.
   Objects already known to be missing on disk are skipped at plan time.
2. **Streaming executor** (``RestoreEngine``): a bounded thread pool
   reads + decompresses + CRC/fingerprint-verifies objects through a
   ``ChunkStore.ReadSession`` (read-once coalescing cache), while the
   main thread places each finished unit on device with
   ``jax.device_put`` — H2D for unit *k* overlaps disk/decode for unit
   *k+1*.  No full zero host tree is ever materialized: stacked layer
   groups assemble into ``np.empty`` buffers, everything else is placed
   straight from the decoded chunk.
3. **Partial/lazy restore**: ``parts=("params",)`` skips optimizer
   objects entirely (they are never read, so bytes-read drops
   accordingly — the serve-from-composite-checkpoint scenario), and
   ``units=("block_00", ...)`` restricts restore to units matching the
   given name prefixes.

Failure semantics match the seed path: an unreadable object (missing or
corrupt) falls back to the unit's most recent *different* object from an
older manifest; only when every candidate fails does ``RestoreError``
surface.  ``RestoreEngine.last_stats`` records which manifest step every
fallen-back unit was recovered from, plus wall time, object/byte read
counts, and dedup savings.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.chunk_store import ChunkRef, ChunkStore, ReadSession
from repro.checkpoint.serial import (
    ChunkCorruption,
    flatten_with_paths,
    unflatten_from_paths,
)
from repro.checkpoint.sharded import (
    WantedFn,
    assemble_shards,
    spec_key,
    spec_overlaps,
)
from repro.core.layer_registry import OPT_KINDS, LayerRegistry
from repro.core.manifest import Manifest, ManifestStore, entry_refs, is_sharded
from repro.optim.groups import get_at, set_at

log = logging.getLogger("repro.checkpoint.restore")

PyTree = Any

PARTS_ALL = ("params", "opt")
# part name -> the manifest entry kind holding its objects
_PART_KIND = {"params": "weights", "opt": "opt"}
DEFAULT_IO_THREADS = 4


class RestoreError(RuntimeError):
    pass


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One readable-object candidate for a (unit, kind) target."""
    manifest_step: int      # the manifest this ref was resolved from
    ref: ChunkRef

    def digests(self) -> Tuple[str, ...]:
        """Digests a read of this candidate touches (object + delta base)."""
        out = [self.ref.digest]
        if self.ref.delta_base:
            out.append(self.ref.delta_base)
        return tuple(out)


@dataclasses.dataclass(frozen=True)
class UnitRead:
    """Read plan for one read target: the primary candidate followed by
    the up-front-resolved older-manifest fallbacks, best first.  For a
    sharded manifest entry there is one target PER SCHEDULED SHARD
    OBJECT (``spec`` carries its ShardSpec); for a classic global entry
    there is exactly one target with ``spec=None``."""
    unit: str
    kind: str               # "weights" | "opt"
    chain: Tuple[Candidate, ...]
    spec: Optional[Dict[str, Any]] = None

    @property
    def primary(self) -> Candidate:
        return self.chain[0]


@dataclasses.dataclass
class RestorePlan:
    step: int                       # manifest step being restored
    meta: Dict[str, Any]
    parts: Tuple[str, ...]
    targets: List[UnitRead]
    # digest -> number of plan dependents (targets + their delta bases),
    # counted over primary candidates: the executor's release schedule.
    dependents: Dict[str, int]
    # sharded entries only: (unit, kind) -> (scheduled, total) shard
    # objects.  scheduled < total means the owned filter skipped shards
    # (the unit assembles zero-filled outside the read blocks).
    shard_groups: Dict[Tuple[str, str], Tuple[int, int]] = \
        dataclasses.field(default_factory=dict)
    shards_skipped: int = 0
    # candidates dropped at plan time because the scrubber quarantined
    # their object (or its delta base) as unrecoverable — the fallback
    # chain skipped the demoted manifests up front.
    quarantined_skipped: int = 0

    @property
    def unique_digests(self) -> int:
        return len(self.dependents)

    @property
    def planned_object_reads(self) -> int:
        """Reads a naive (no-dedup) executor would issue for the same
        targets: one per target object plus one per delta-base replay."""
        return sum(len(t.primary.digests()) for t in self.targets)


def _select_units(unit_names: Sequence[str],
                  units: Optional[Sequence[str]]) -> List[str]:
    """Filter unit names by exact-or-prefix match (``units=None`` = all).
    A bare string is one pattern, not an iterable of characters."""
    if units is None:
        return list(unit_names)
    pats = (units,) if isinstance(units, str) else tuple(units)
    out = [n for n in unit_names if any(n == p or n.startswith(p)
                                        for p in pats)]
    if not out:
        raise RestoreError(f"unit filter {units!r} matches no units")
    return out


def plan_restore(manifests: ManifestStore, store: ChunkStore,
                 unit_names: Sequence[str], *,
                 step: Optional[int] = None,
                 parts: Sequence[str] = PARTS_ALL,
                 units: Optional[Sequence[str]] = None,
                 owned: Optional[WantedFn] = None,
                 manifest: Optional[Manifest] = None) -> RestorePlan:
    """Resolve the manifest chain into a deduplicated, fallback-aware
    read plan.

    For every selected (unit, kind) the plan holds a candidate chain:
    the target manifest's entry first, then — resolved now, not when a
    read fails — every *different* object an older manifest still holds
    for that unit, newest first.  Candidates whose object file (or delta
    base) is already missing on disk are dropped here, so a deleted
    object costs a ``stat`` at plan time instead of a failed read later.

    Sharded entries plan one target per shard object; ``owned`` (a
    ``wanted(unit, kind, path, shape) -> blocks`` resolver, see
    :func:`repro.checkpoint.sharded.participant_wanted`) restricts the
    plan to shard objects whose blocks intersect the caller's slices —
    the slice-aware resharding read plan.  Fallback candidates for a
    shard are older-manifest shards with the SAME layout (equal
    ``spec_key``); a global object never substitutes for one shard.
    """
    parts = tuple(parts)
    for p in parts:
        if p not in PARTS_ALL:
            raise RestoreError(f"unknown restore part {p!r}; "
                               f"expected subset of {PARTS_ALL}")
    if not parts:
        raise RestoreError("restore needs at least one part")
    # ``manifest`` restores from a caller-supplied (possibly synthetic)
    # manifest instead of loading one by ``step`` — the variant-serving
    # path (``core.tailor.variant_manifest``): entries are picked from
    # several committed manifests of the SAME store, and the older-
    # manifest fallback chains of that store still apply.
    if manifest is None:
        manifest = manifests.load(step)
    if manifest is None:
        raise RestoreError(f"no manifest found in {manifests.root}")

    # One pass over the retained manifest chain, oldest -> newest, keeping
    # every older-step entry per (unit, kind).  This is the up-front
    # version of the seed path's per-unit fallback crawl.
    older: Dict[Tuple[str, str], List[Tuple[int, Any]]] = {}
    for s in manifests.all_steps():
        if s >= manifest.step:
            continue
        m = manifests.load(s)
        if m is None:
            continue
        for unit, kinds in m.entries.items():
            for kind, entry in kinds.items():
                older.setdefault((unit, kind), []).append((s, entry))

    quarantined_skipped = [0]  # mutated by readable() below

    def readable(c: Candidate) -> bool:
        """Plan-time liveness: digest present and (if delta) base present.
        Undiscovered corruption is only findable at read time — the
        executor walks the remaining chain for that — but corruption the
        scrubber already PROVED unrecoverable (quarantined digests) is
        rejected here, so demoted manifests never enter a chain."""
        if not c.ref.digest or not store.has(c.ref.digest):
            return False
        if (store.quarantined(c.ref.digest)
                or (c.ref.delta_base
                    and store.quarantined(c.ref.delta_base))):
            quarantined_skipped[0] += 1
            return False
        return not c.ref.delta_base or store.has(c.ref.delta_base)

    def resolve_chain(name: str, kind: str, primary: Candidate,
                      fallbacks: List[Candidate]) -> Optional[Tuple]:
        chain: List[Candidate] = []
        seen: set = set()
        for c in [primary] + fallbacks:
            key = c.ref.digest or c.ref.relpath
            if key in seen:
                continue  # same object — would fail identically
            seen.add(key)
            if not readable(c):
                if c is primary:
                    log.warning(
                        "object for %s/%s at step %s missing on disk; "
                        "fallback resolved at plan time",
                        name, kind, c.ref.step)
                continue
            chain.append(c)
        return tuple(chain) if chain else None

    selected = _select_units(unit_names, units)
    kinds = tuple(_PART_KIND[p] for p in parts)
    targets: List[UnitRead] = []
    dependents: Dict[str, int] = {}
    shard_groups: Dict[Tuple[str, str], Tuple[int, int]] = {}
    shards_skipped = 0

    def add_target(t: UnitRead) -> None:
        targets.append(t)
        for d in t.primary.digests():
            dependents[d] = dependents.get(d, 0) + 1

    for name in selected:
        if name not in manifest.entries:
            raise RestoreError(f"manifest missing unit {name}")
        for kind in kinds:
            entry = manifest.entries[name][kind]
            past = older.get((name, kind), [])
            if not is_sharded(entry):
                fallbacks = [Candidate(s, e)
                             for s, e in reversed(past)
                             if not is_sharded(e)]
                chain = resolve_chain(name, kind,
                                      Candidate(manifest.step, entry),
                                      fallbacks)
                if chain is None:
                    raise RestoreError(f"no readable chunk for unit "
                                       f"{name}/{kind}")
                add_target(UnitRead(name, kind, chain))
                continue

            refs = entry_refs(entry)
            # One pass over the older entries builds the layout-keyed
            # fallback index; per-ref lookup is then O(1) instead of
            # rescanning (and re-hashing specs of) every older manifest
            # per shard ref.
            older_by_layout: Dict[Tuple, List[Candidate]] = {}
            for s, e in reversed(past):
                if not is_sharded(e):
                    continue
                for r in entry_refs(e):
                    if r.spec is not None:
                        older_by_layout.setdefault(
                            spec_key(r.spec), []).append(Candidate(s, r))
            shard_targets: List[UnitRead] = []
            # per target: manifest step -> readable candidate serving
            # that step's content.  An unchanged shard's entry dedups to
            # the same digest across steps, so ONE object can serve
            # several steps — the step map (not the digest chain) is
            # what unit-consistent alignment must reason over.
            step_maps: List[Dict[int, Candidate]] = []
            for ref in refs:
                if ref.spec is None:
                    raise RestoreError(
                        f"sharded entry for {name}/{kind} has a ref "
                        "without a shard spec — manifest is corrupt")
                if owned is not None and not spec_overlaps(ref.spec, owned,
                                                           name, kind):
                    shards_skipped += 1
                    continue
                cands = ([Candidate(manifest.step, ref)]
                         + older_by_layout.get(spec_key(ref.spec), []))
                chain: List[Candidate] = []
                steps: Dict[int, Candidate] = {}
                seen: set = set()
                for c in cands:  # newest step first
                    if not readable(c):
                        if c is cands[0]:
                            log.warning(
                                "shard object for %s/%s at step %s "
                                "missing on disk; fallback resolved at "
                                "plan time", name, kind, c.manifest_step)
                        continue
                    steps[c.manifest_step] = c
                    if c.ref.digest not in seen:
                        seen.add(c.ref.digest)
                        chain.append(c)
                if not chain:
                    raise RestoreError(
                        f"no readable shard object for unit {name}/{kind} "
                        f"(participant {ref.spec.get('participant')})")
                shard_targets.append(UnitRead(name, kind, tuple(chain),
                                              spec=ref.spec))
                step_maps.append(steps)
            # Unit-consistent fallback: if any shard's plan-time primary
            # fell behind the target step, anchor EVERY scheduled shard
            # of this unit on the newest step all of them can serve —
            # never assemble one tensor from mixed manifest steps (a
            # state that never existed).  No common step at all is an
            # error: serving a torn tensor silently would be worse than
            # failing the restore.  (Read-time corruption can still walk
            # each chain's remainder — the documented narrow window.)
            if (len(shard_targets) > 1
                    and any(t.primary.manifest_step != manifest.step
                            for t in shard_targets)):
                common = set.intersection(*(set(m) for m in step_maps))
                if not common:
                    raise RestoreError(
                        f"unit {name}/{kind}: no single manifest step is "
                        "readable by every shard — refusing to assemble "
                        "a mixed-step tensor")
                best = max(common)
                shard_targets = [
                    dataclasses.replace(
                        t, chain=(m[best],) + tuple(
                            c for c in t.chain
                            if c.ref.digest != m[best].ref.digest))
                    for t, m in zip(shard_targets, step_maps)]
                log.warning(
                    "unit %s/%s: aligning all %d shards on manifest "
                    "step %s (newest step readable by every shard)",
                    name, kind, len(shard_targets), best)
            for t in shard_targets:
                add_target(t)
            shard_groups[(name, kind)] = (len(shard_targets), len(refs))
    return RestorePlan(step=manifest.step, meta=dict(manifest.meta),
                       parts=parts, targets=targets, dependents=dependents,
                       shard_groups=shard_groups,
                       shards_skipped=shards_skipped,
                       quarantined_skipped=quarantined_skipped[0])


class _Placer:
    """Incremental host-assembly + device placement.

    Units arrive in completion order.  A unit that owns a whole params
    subtree is placed on device immediately (``jax.device_put`` is
    asynchronous, so its H2D transfer overlaps the reads still in
    flight).  Units that are slices of a stacked layer group fill a
    shared ``np.empty`` buffer; the group is placed once its last slice
    lands.  Nothing is ever zero-filled unless a unit filter left real
    holes (partial stacked restore), and the seed path's full-model
    ``np.zeros`` tree is gone entirely.
    """

    def __init__(self, registry: LayerRegistry, state_like: Dict[str, PyTree],
                 shardings: Optional[Dict[str, PyTree]],
                 plan: RestorePlan):
        self.registry = registry
        self.state_like = state_like
        self.shardings = shardings
        self.parts = plan.parts
        # root path (from the state dict) -> placed device subtree
        self._placed: Dict[Tuple[str, ...], PyTree] = {}
        # stacked groups: root path -> {"bufs", "remaining", "partial"}
        self._groups: Dict[Tuple[str, ...], Dict[str, Any]] = {}
        self.h2d_bytes = 0

        # Shard accumulation: (unit, kind) -> the decoded shard parts
        # still outstanding.  The assembled unit enters the ordinary
        # placement path (stacked groups, device_put) once its last
        # scheduled shard lands; scheduled < total (owned-filtered plan)
        # assembles zero-filled outside the read blocks.
        self._shards: Dict[Tuple[str, str], Dict[str, Any]] = {
            key: {"remaining": scheduled, "total": total, "parts": []}
            for key, (scheduled, total) in plan.shard_groups.items()
            if scheduled > 0}

        # Pre-size stacked groups from the plan so a partial restore of a
        # group is detectable (its buffers must start zeroed, not empty).
        # Sharded entries contribute several targets per (unit, kind) but
        # place exactly once — count unique pairs.
        want: Dict[Tuple[str, ...], int] = {}
        for unit, kind in dict.fromkeys((t.unit, t.kind)
                                        for t in plan.targets):
            u = registry.by_name[unit]
            if u.index is None:
                continue
            for root in self._roots(unit, kind):
                want[root] = want.get(root, 0) + 1
        total: Dict[Tuple[str, ...], int] = {}
        for uu in registry.units:
            if uu.index is None:
                continue
            for kind in ("weights", "opt"):
                for root in self._roots(uu.name, kind):
                    total[root] = total.get(root, 0) + 1
        for root, n in want.items():
            self._groups[root] = {"bufs": None, "remaining": n,
                                  "partial": n < total.get(root, n)}

    def _roots(self, unit: str, kind: str) -> List[Tuple[str, ...]]:
        """State-dict root paths a (unit, kind) read assigns into."""
        u = self.registry.by_name[unit]
        if kind == "weights":
            return [("params",) + u.path]
        return [("opt", k) + u.path for k in OPT_KINDS]

    def _subtrees(self, unit: str, kind: str, tree: PyTree
                  ) -> List[Tuple[Tuple[str, ...], PyTree]]:
        u = self.registry.by_name[unit]
        if kind == "weights":
            return [(("params",) + u.path, tree)]
        return [(("opt", k) + u.path, tree[k]) for k in OPT_KINDS]

    def _put(self, root: Tuple[str, ...], host: PyTree) -> PyTree:
        self.h2d_bytes += int(sum(np.asarray(x).nbytes
                                  for x in jax.tree.leaves(host)))
        if self.shardings is not None:
            return jax.tree.map(jax.device_put, host,
                                get_at(self.shardings, root))
        return jax.tree.map(jnp.asarray, host)

    def add_shard(self, unit: str, kind: str, spec: Dict[str, Any],
                  tree: PyTree) -> None:
        """Accumulate one decoded shard object; assemble + place the
        unit once its last scheduled shard arrives."""
        g = self._shards[(unit, kind)]
        g["parts"].append((spec, tree))
        g["remaining"] -= 1
        if g["remaining"] == 0:
            partial = len(g["parts"]) < g["total"]
            assembled = assemble_shards(g["parts"], partial=partial)
            g["parts"] = []
            if partial:
                assembled = self._fill_unread_leaves(unit, kind, assembled)
            self.add(unit, kind, assembled)

    def _fill_unread_leaves(self, unit: str, kind: str,
                            tree: PyTree) -> PyTree:
        """Complete an owned-filtered assembly: a leaf none of whose shard
        objects overlap the caller's slices was never read, and restores
        as zeros like every other unread block."""
        lead = 1 if self.registry.by_name[unit].index is not None else 0
        likes = [jax.tree.map(
            lambda s: np.zeros(tuple(s.shape)[lead:], s.dtype),
            get_at(self.state_like, root))
            for root in self._roots(unit, kind)]
        full = likes[0] if kind == "weights" else dict(zip(OPT_KINDS, likes))
        have = dict(flatten_with_paths(tree))
        return unflatten_from_paths({
            path: have.get(path, zero)
            for path, zero in flatten_with_paths(full)})

    def add(self, unit: str, kind: str, tree: PyTree) -> None:
        u = self.registry.by_name[unit]
        for root, sub in self._subtrees(unit, kind, tree):
            if u.index is None:
                self._placed[root] = self._put(root, sub)
                continue
            g = self._groups[root]
            if g["bufs"] is None:
                spec = get_at(self.state_like, root)
                alloc = np.zeros if g["partial"] else np.empty
                g["bufs"] = jax.tree.map(
                    lambda s: alloc(s.shape, s.dtype), spec)

            def fill(buf, piece):
                buf[u.index] = np.asarray(piece, buf.dtype)
                return buf

            jax.tree.map(fill, g["bufs"], sub)
            g["remaining"] -= 1
            if g["remaining"] == 0:
                self._placed[root] = self._put(root, g["bufs"])
                g["bufs"] = None

    def finish(self, step: int) -> Dict[str, PyTree]:
        """Assemble the output state from placed subtrees.  Leaves no
        unit covers (possible only under a unit filter, or for model
        families whose params hold leaves outside every registry unit)
        restore as zeros — the seed-path semantics."""
        out: Dict[str, PyTree] = {}
        for part in self.parts:
            # Demote concrete state_like leaves to shape/dtype specs: a
            # leaf no placed subtree overwrites must restore as zeros
            # (seed semantics), never leak the caller's array values.
            out[part] = jax.tree.map(
                lambda x: x if isinstance(x, jax.ShapeDtypeStruct)
                else jax.ShapeDtypeStruct(np.shape(x), x.dtype),
                self.state_like[part])
        for root, placed in self._placed.items():
            out = set_at(out, root, placed)

        # Backfill leaves no placed subtree covered with zeros, honoring
        # the target shardings (an elastic partial restore must not mix
        # mesh-sharded units with default-device zeros).
        for part in self.parts:
            if self.shardings is not None:
                out[part] = jax.tree.map(
                    lambda x, s: jax.device_put(
                        np.zeros(x.shape, x.dtype), s)
                    if isinstance(x, jax.ShapeDtypeStruct) else x,
                    out[part], self.shardings[part])
            else:
                out[part] = jax.tree.map(
                    lambda x: jnp.zeros(x.shape, x.dtype)
                    if isinstance(x, jax.ShapeDtypeStruct) else x,
                    out[part])
        step_arr = np.asarray(step, np.int32)
        if self.shardings is not None and "step" in self.shardings:
            out["step"] = jax.device_put(step_arr, self.shardings["step"])
        else:
            out["step"] = jnp.asarray(step_arr)
        return out


class RestoreEngine:
    """Executes a :class:`RestorePlan` as a streaming pipeline.

    ``io_threads`` bounds the read/decode pool; ``pipelined=False`` (or
    ``io_threads <= 1``) runs the identical plan strictly sequentially —
    the comparison arm ``bench_ckpt_time`` measures and the bit-exactness
    tests pin against.  ``verify`` toggles read-time integrity checking:
    per-tensor CRC32 on v1 objects and the PR-2 fingerprint-table
    recompute on fp-addressed objects (restore-time fingerprint
    verification against the stored tables).  ``verify=False`` skips
    both for maximum-bandwidth trusted-storage restores.
    """

    def __init__(self, store: ChunkStore, manifests: ManifestStore,
                 registry: LayerRegistry, *,
                 io_threads: int = DEFAULT_IO_THREADS, verify: bool = True):
        self.store = store
        self.manifests = manifests
        self.registry = registry
        self.io_threads = max(1, int(io_threads))
        self.verify = verify
        self.last_stats: Dict[str, Any] = {}

    # ------------------------------------------------------------- execute
    def _read_target(self, target: UnitRead, session: ReadSession,
                     plan_step: int, fallbacks: Dict[str, int],
                     tiers: Dict[str, str]
                     ) -> Tuple[UnitRead, PyTree]:
        # Process-backed dispatch moves the decompress+verify stage of
        # each read into a subprocess worker; the delta base (full by
        # store invariant) comes from the manifest ref, so the parent
        # never parses envelopes just to discover it.
        offload = self.store.dispatch.is_process
        last_exc: Optional[Exception] = None
        for cand in target.chain:
            try:
                if offload:
                    tree, _ = session.read_offload(cand.ref.digest,
                                                   cand.ref.delta_base)
                else:
                    tree, _ = session.read(cand.ref.digest)
                tier = session.tiers.get(cand.ref.digest)
                if tier is not None:
                    tiers[f"{target.unit}/{target.kind}"] = tier
            except (FileNotFoundError, ChunkCorruption) as e:
                log.warning("chunk %s/%s from manifest %s unreadable (%s); "
                            "falling back", target.unit, target.kind,
                            cand.manifest_step, e)
                last_exc = e
                continue
            if cand.manifest_step != plan_step:
                # Covers both read-time fallbacks and candidates the
                # planner promoted because the target manifest's object
                # was already missing on disk.
                log.warning(
                    "unit %s/%s restored from older manifest %s (tier=%s)",
                    target.unit, target.kind, cand.manifest_step,
                    session.tiers.get(cand.ref.digest))
                fallbacks[f"{target.unit}/{target.kind}"] = cand.manifest_step
            return target, tree
        raise RestoreError(
            f"no readable chunk for unit {target.unit}/{target.kind}"
        ) from last_exc

    def restore(self, state_like: Dict[str, PyTree], *,
                step: Optional[int] = None,
                shardings: Optional[Dict[str, PyTree]] = None,
                parts: Sequence[str] = PARTS_ALL,
                units: Optional[Sequence[str]] = None,
                pipelined: bool = True,
                owned: Optional[WantedFn] = None,
                manifest: Optional[Manifest] = None) -> Dict[str, PyTree]:
        """Rebuild a train state from the manifest chain (the implicit
        Frankenstein merge), streaming units device-ward as they decode.

        ``state_like`` supplies structure/dtypes (arrays or
        ShapeDtypeStructs) for the requested ``parts``; ``shardings``
        optionally places every unit onto a mesh as it lands (elastic
        restart onto any device count).  ``parts``/``units`` select a
        subset (weights-only serving, per-unit-prefix surgery); ``owned``
        restricts sharded entries to the shard objects intersecting the
        caller's slices (per-participant resharded restore — uncovered
        regions of those units restore as zeros); the returned dict holds
        exactly the requested parts plus ``step``.
        """
        t0 = time.time()
        io_retries0 = self.store.io_retries
        dispatch = self.store.dispatch
        workers0 = dispatch.stats()  # None under the thread backend
        plan = plan_restore(self.manifests, self.store,
                            self.registry.unit_names(), step=step,
                            parts=parts, units=units, owned=owned,
                            manifest=manifest)
        session = ReadSession(self.store, verify=self.verify)
        placer = _Placer(self.registry, state_like, shardings, plan)
        fallbacks: Dict[str, int] = {}
        # unit/kind -> tier its object was served from ("hot"/"durable"/
        # "local"/...): the tier dimension of restore provenance.
        unit_tiers: Dict[str, str] = {}
        remaining = dict(plan.dependents)

        def consume(target: UnitRead, tree: PyTree) -> None:
            if target.spec is not None:
                placer.add_shard(target.unit, target.kind, target.spec,
                                 tree)
            else:
                placer.add(target.unit, target.kind, tree)
            # Release session memory for digests no plan target still
            # needs (fallback digests are not tracked — rare, and freed
            # when the session goes out of scope).
            for d in target.primary.digests():
                n = remaining.get(d)
                if n is not None:
                    if n <= 1:
                        remaining.pop(d, None)
                        session.release(d)
                    else:
                        remaining[d] = n - 1

        run_parallel = pipelined and self.io_threads > 1 \
            and len(plan.targets) > 1
        if run_parallel:
            with ThreadPoolExecutor(
                    max_workers=self.io_threads,
                    thread_name_prefix="ckpt-restore") as pool:
                futs = {pool.submit(self._read_target, t, session,
                                    plan.step, fallbacks, unit_tiers)
                        for t in plan.targets}
                try:
                    while futs:
                        done, futs = wait(futs, return_when=FIRST_COMPLETED)
                        for f in done:
                            consume(*f.result())
                except BaseException:
                    for f in futs:
                        f.cancel()
                    raise
        else:
            for t in plan.targets:
                consume(*self._read_target(t, session, plan.step,
                                           fallbacks, unit_tiers))
        state = placer.finish(plan.step)
        jax.block_until_ready(
            [x for part in plan.parts for x in jax.tree.leaves(state[part])])
        self.last_stats = {
            "step": plan.step,
            "seconds": time.time() - t0,
            "parts": list(plan.parts),
            "units": len({t.unit for t in plan.targets}),
            "targets": len(plan.targets),
            "pipelined": run_parallel,
            "io_threads": self.io_threads if run_parallel else 1,
            "verify": self.verify,
            # read accounting (the dedup win: objects_read <= targets)
            "bytes_read": session.stats["bytes_read"],
            "objects_read": session.stats["object_reads"],
            "unique_digests": plan.unique_digests,
            "planned_object_reads": plan.planned_object_reads,
            "h2d_bytes": placer.h2d_bytes,
            # shard-native accounting: how many targets were shard
            # objects, and how many the owned filter skipped (the
            # resharding read-savings the tests pin down)
            "sharded_targets": sum(1 for t in plan.targets
                                   if t.spec is not None),
            "shards_skipped": plan.shards_skipped,
            # unit/kind -> manifest step it actually came from (only
            # entries that fell back from the target manifest)
            "fallback_units": fallbacks,
            # transient backend-read errors a bounded retry absorbed
            # during THIS restore — distinct from fallbacks, which burn
            # a manifest candidate (satellite: flaky != corrupt)
            "io_retries": self.store.io_retries - io_retries0,
            # plan-time candidates dropped because the scrubber had
            # quarantined their object as unrecoverable
            "quarantined_skipped": plan.quarantined_skipped,
            # tier provenance: aggregate object reads per tier, plus the
            # tier every unit/kind (fallbacks included) was served from
            "tier_reads": dict(session.tier_reads),
            "unit_tiers": unit_tiers,
            # which worker backend decoded the bytes, and (process only)
            # this restore's share of the worker traffic
            "io_backend": dispatch.backend,
        }
        if workers0 is not None:
            w1 = dispatch.stats() or {"lanes": {}, "worker_restarts": 0}
            lane0 = workers0["lanes"].get("restore",
                                          {"tasks": 0, "bytes_shm": 0})
            lane1 = w1["lanes"].get("restore", {"tasks": 0, "bytes_shm": 0})
            self.last_stats["workers"] = {
                "tasks": lane1["tasks"] - lane0["tasks"],
                "bytes_shm": lane1["bytes_shm"] - lane0["bytes_shm"],
                "worker_restarts": w1["worker_restarts"],
            }
        return state

"""Shard-native checkpointing: block math, the slice-intersection
property (planned reads exactly cover the target's addressable indices),
the two-phase commit barrier (crash-injected), resharded restores that
read strictly fewer bytes, shard-set merges, and the mesh subprocess
path (save on 1x8 -> restore on 2x4)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from proptest import cases, rand_shape
from repro.checkpoint.saver import CheckpointManager
from repro.checkpoint.sharded import (
    ShardBarrierError,
    ShardCoordinator,
    ShardedCheckpointer,
    ShardedSaver,
    combine_states,
    participant_wanted,
    spec_overlaps,
)
from repro.configs import get_config
from repro.core import LayerRegistry, Recipe, make_policy, merge
from repro.core.manifest import entry_refs, is_sharded
from repro.core.policies import PolicyContext
from repro.core.recipe import CheckpointRef
from repro.launch import steps as steps_lib
from repro.models import build_model
from repro.models.model_api import LayerUnit
from repro.parallel import sharding as shd

SRC = str(Path(__file__).resolve().parent.parent / "src")


# ------------------------------------------------------------- block math
def test_block_math_basics():
    a = ((0, 4), (0, 8))
    b = ((2, 6), (4, 12))
    assert shd.intersect_blocks(a, b) == ((2, 4), (4, 8))
    assert shd.intersect_blocks(a, ((4, 6), (0, 8))) is None
    assert shd.block_size(a) == 32
    assert shd.block_size(()) == 1  # scalar block
    assert shd.blocks_cover_exactly((4, 8), [((0, 2), (0, 8)),
                                             ((2, 4), (0, 8))])
    # overlap -> not a cover
    assert not shd.blocks_cover_exactly((4, 8), [((0, 3), (0, 8)),
                                                 ((2, 4), (0, 8))])
    # hole -> not a cover
    assert not shd.blocks_cover_exactly((4, 8), [((0, 2), (0, 8))])


def test_uniform_blocks_partition_exactly():
    for shape, n in cases(40, lambda rs: (rand_shape(rs, dim_max=13),
                                          int(rs.randint(1, 6)))):
        blocks = [b for pid in range(n)
                  for b in shd.uniform_blocks(shape, pid, n)]
        assert shd.blocks_cover_exactly(shape, blocks), (shape, n, blocks)


def _grid_partition(rs, shape):
    """Random grid tiling of ``shape``: per-dim random cut points ->
    rectangular blocks covering the array exactly."""
    if not shape:
        return [()]
    per_dim = []
    for d in shape:
        n_cuts = rs.randint(0, min(3, d))
        cuts = sorted(set([0, d] + list(rs.randint(1, d, size=n_cuts))
                          if d > 1 else [0, d]))
        per_dim.append([(cuts[i], cuts[i + 1])
                        for i in range(len(cuts) - 1)])
    blocks = [()]
    for ranges in per_dim:
        blocks = [b + (r,) for b in blocks for r in ranges]
    return blocks


def _assign(rs, blocks, k):
    """Distribute blocks over k owners (every block exactly one owner)."""
    owners = [[] for _ in range(k)]
    for b in blocks:
        owners[rs.randint(0, k)].append(b)
    return [tuple(o) for o in owners]


def test_slice_plan_covers_target_exactly():
    """Satellite property: for random global shapes, source shardings
    (random grid tilings grouped into shard objects), and target
    shardings (another random tiling grouped into participants), the
    union of planned reads exactly covers each target participant's
    addressable indices — no holes, no double-reads — and every skipped
    shard is genuinely disjoint from the target."""

    def gen(rs):
        shape = rand_shape(rs, ndim_max=3, dim_max=9)
        n_src = int(rs.randint(1, 5))
        n_tgt = int(rs.randint(1, 5))
        src = _assign(rs, _grid_partition(rs, shape), n_src)
        tgt = _assign(rs, _grid_partition(rs, shape), n_tgt)
        return shape, src, tgt

    for shape, src_shards, tgt_parts in cases(60, gen, seed=77):
        # the source shards must themselves tile the array (sanity on
        # the generator — the same invariant the coordinator checks)
        all_src = [b for s in src_shards for b in s]
        assert shd.blocks_cover_exactly(shape, all_src)
        specs = [{"participant": i,
                  "leaves": [{"path": "w", "shape": list(shape),
                              "dtype": "float32",
                              "blocks": [list(map(list, b))
                                         for b in blocks]}]}
                 for i, blocks in enumerate(src_shards) if blocks]
        for want in tgt_parts:
            def wanted(unit, kind, path, s, _want=want):
                return _want

            planned = [sp for sp in specs
                       if spec_overlaps(sp, wanted, "u", "weights")]
            skipped = [sp for sp in specs if sp not in planned]
            # planned reads cover the wanted region exactly: the
            # intersections tile it (sizes sum; disjoint by source
            # disjointness)
            pieces = []
            for sp in planned:
                for leaf in sp["leaves"]:
                    for b in leaf["blocks"]:
                        blk = tuple((int(x), int(y)) for x, y in b)
                        for w in want:
                            inter = shd.intersect_blocks(blk, w)
                            if inter:
                                pieces.append(inter)
            want_size = sum(shd.block_size(w) for w in want)
            got = sum(shd.block_size(p) for p in pieces)
            assert got == want_size, (shape, want, pieces)
            for i, p in enumerate(pieces):  # no double-reads
                for q in pieces[i + 1:]:
                    assert not shd.intersect_blocks(p, q), (p, q)
            # nothing skipped that overlapped
            for sp in skipped:
                for leaf in sp["leaves"]:
                    for b in leaf["blocks"]:
                        blk = tuple((int(x), int(y)) for x, y in b)
                        for w in want:
                            assert not shd.intersect_blocks(blk, w)


# ------------------------------------------------------- save/restore paths
@pytest.fixture(scope="module")
def small_setup():
    cfg = get_config("mamba2-370m", reduced=True)
    model = build_model(cfg)
    state = steps_lib.init_state(model, jax.random.key(0))
    return model, state, LayerRegistry(model)


def _assert_state_equal(a, b, parts=("params", "opt")):
    for key in parts:
        for x, y in zip(jax.tree.leaves(a[key]), jax.tree.leaves(b[key])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_sharded_save_restart_restore_roundtrip(small_setup, tmp_path):
    model, state, reg = small_setup
    mgr = CheckpointManager(tmp_path, reg,
                            make_policy("parity", model.layer_units()))
    ck = ShardedCheckpointer(mgr, 2)
    m1 = ck.save(state, step=10)
    assert all(is_sharded(e) for kinds in m1.entries.values()
               for e in kinds.values())
    assert m1.meta["sharded"]["n_participants"] == 2
    # every shard ref carries a spec whose participant wrote it
    pids = {r.spec["participant"] for kinds in m1.entries.values()
            for e in kinds.values() for r in entry_refs(e)}
    assert pids == {0, 1}
    # unchanged re-save: pure fingerprint dedup, zero payload transfer
    ck.save(state, step=20)
    s = mgr.last_save_stats
    assert s["written_bytes"] == 0 and s["d2h_bytes"] == 0
    assert s["dedup_hits"] > 0
    mgr.close()

    # restart: fresh manager (fp refs cold) restores the chain bit-exact
    mgr2 = CheckpointManager(tmp_path, reg,
                             make_policy("parity", model.layer_units()),
                             async_save=False)
    restored = mgr2.restore(steps_lib.state_specs(model))
    _assert_state_equal(state, restored)
    assert int(restored["step"]) == 20
    assert not mgr2.last_restore_stats["fallback_units"]
    # and a restarted participant still dedups (fp table reloaded from
    # the object envelope)
    ck2 = ShardedCheckpointer(mgr2, 2)
    ck2.save(state, step=30)
    s = mgr2.last_save_stats
    assert s["written_bytes"] == 0 and s["d2h_bytes"] == 0
    mgr2.close()


def test_resharded_restore_reads_strictly_fewer_bytes(small_setup, tmp_path):
    model, state, reg = small_setup
    mgr = CheckpointManager(tmp_path, reg,
                            make_policy("full", model.layer_units()))
    ck = ShardedCheckpointer(mgr, 2)
    ck.save(state, step=10)
    like = steps_lib.state_specs(model)
    mgr.restore(like)
    full = dict(mgr.last_restore_stats)
    assert full["sharded_targets"] > 0 and full["shards_skipped"] == 0

    results, wanteds = [], []
    for pid in range(4):
        wanted = participant_wanted(reg, pid, 4)
        results.append(mgr.restore(like, owned=wanted))
        s = mgr.last_restore_stats
        assert s["bytes_read"] < full["bytes_read"]
        assert s["shards_skipped"] > 0
        wanteds.append(wanted)
    mgr.close()
    combined = combine_states(like, reg, results, wanteds)
    _assert_state_equal(state, combined)
    assert int(combined["step"]) == 10


def test_block_delta_per_shard_object(tmp_path):
    """Small drift in a big unit takes the BD02 block-sparse delta path
    PER SHARD OBJECT: only the dirty blocks of the owning participant's
    shard move device->host and land as a block delta against that
    shard's own full base."""
    cfg = get_config("llama3.2-3b", reduced=True)
    model = build_model(cfg)
    state = steps_lib.init_state(model, jax.random.key(0))
    reg = LayerRegistry(model)
    # 4 KiB fingerprint blocks: the reduced model's shards span many
    # blocks, so a one-element poke stays under fp_max_dirty_frac.
    mgr = CheckpointManager(tmp_path, reg,
                            make_policy("full", model.layer_units()),
                            fp_block_bytes=4096)
    ck = ShardedCheckpointer(mgr, 2)
    ck.save(state, step=10)

    def poke(x):
        x = np.array(x)
        x.flat[0] += 1
        return x

    drifted = dict(state)
    drifted["params"] = jax.tree.map(poke, jax.device_get(state["params"]))
    ck.save(drifted, step=20)
    s = mgr.last_save_stats
    assert s["delta_chunks"] > 0, s
    assert s["dirty_block_frac"] < 0.05, s
    assert s["dedup_hits"] > 0  # untouched shards (and all opt) dedup
    restored = mgr.restore(steps_lib.state_specs(model))
    _assert_state_equal(drifted, restored)
    mgr.close()


def test_non_fingerprint_sharded_path(small_setup, tmp_path):
    """The legacy full-gather path also works shard-native (XOR deltas
    per shard object on later events)."""
    model, state, reg = small_setup
    mgr = CheckpointManager(tmp_path, reg,
                            make_policy("full", model.layer_units()),
                            fingerprint=False)
    ck = ShardedCheckpointer(mgr, 2)
    ck.save(state, step=10)
    drifted = dict(state)
    drifted["params"] = jax.tree.map(lambda x: x + np.ones((), x.dtype),
                                     state["params"])
    ck.save(drifted, step=20)
    assert mgr.last_save_stats["delta_chunks"] > 0, \
        "drifted shard objects should delta-encode against their bases"
    restored = mgr.restore(steps_lib.state_specs(model))
    _assert_state_equal(drifted, restored)
    mgr.close()


def test_sharded_gc_retention(small_setup, tmp_path):
    """Refcounted retention over shard sets: dropped manifests release
    one reference per shard ref (and delta base); objects only die when
    no retained manifest references them."""
    model, state, reg = small_setup
    mgr = CheckpointManager(tmp_path, reg,
                            make_policy("full", model.layer_units()),
                            keep=2, async_save=False)
    ck = ShardedCheckpointer(mgr, 2, parallel=False)
    rng = np.random.RandomState(0)
    for i in range(4):
        drifted = dict(state)
        drifted["params"] = jax.tree.map(
            lambda x: np.asarray(jax.device_get(x))
            + np.asarray(rng.standard_normal(), np.asarray(x).dtype),
            state["params"])
        ck.save(drifted, step=(i + 1) * 10)
    assert mgr.manifests.all_steps() == [30, 40]
    # every object a retained manifest references is still present...
    live = set()
    for s in (30, 40):
        live |= set(mgr.manifests.load(s).referenced_digests())
    for d in live:
        assert mgr.store.has(d)
    # ...and nothing else survived GC
    on_disk = set(mgr.store.iter_digests())
    assert on_disk == live
    restored = mgr.restore(steps_lib.state_specs(model))
    _assert_state_equal(drifted, restored)
    mgr.close()


def test_barrier_crash_keeps_previous_manifest(small_setup, tmp_path):
    model, state, reg = small_setup
    mgr = CheckpointManager(tmp_path, reg,
                            make_policy("full", model.layer_units()),
                            async_save=False)
    ck = ShardedCheckpointer(mgr, 2)
    ck.save(state, step=10)

    # Crash injection: participant 0 publishes its record for step 20,
    # participant 1 dies before publishing.  The coordinator must refuse
    # and the previous manifest stays authoritative.
    ShardedSaver(mgr, 0, 2).save_shards(state, step=20)
    coord = ShardCoordinator(mgr)
    with pytest.raises(ShardBarrierError, match="missing participant"):
        coord.commit(20, 2)
    restored = mgr.restore(steps_lib.state_specs(model))
    assert int(restored["step"]) == 10
    _assert_state_equal(state, restored)

    # Recovery: the restarted participant re-publishes, commit succeeds.
    ShardedSaver(mgr, 1, 2).save_shards(state, step=20)
    manifest = coord.commit(20, 2)
    assert manifest.step == 20
    restored = mgr.restore(steps_lib.state_specs(model))
    assert int(restored["step"]) == 20
    _assert_state_equal(state, restored)
    mgr.close()


def test_event_index_survives_retention_cap(small_setup, tmp_path):
    """The event counter anchors on the newest manifest's recorded
    index, NOT the retained-manifest count: with keep=2 a parity policy
    must keep alternating halves past the retention horizon (counting
    manifests would saturate at 2 and freeze one half forever)."""
    model, state, reg = small_setup
    mgr = CheckpointManager(tmp_path, reg,
                            make_policy("parity", model.layer_units()),
                            keep=2, async_save=False)
    ck = ShardedCheckpointer(mgr, 2, parallel=False)
    selections = []
    for i in range(6):
        m = ck.save(state, step=(i + 1) * 10)
        selections.append((m.meta["event_index"], tuple(m.saved_units)))
    idxs = [i for i, _ in selections]
    assert idxs == list(range(6))
    # consecutive events past the cap still alternate
    assert selections[-1][1] != selections[-2][1]
    # and a restarted manager resumes the counter, not the manifest count
    mgr.close()
    mgr2 = CheckpointManager(tmp_path, reg,
                             make_policy("parity", model.layer_units()),
                             keep=2, async_save=False)
    m = ShardedCheckpointer(mgr2, 2, parallel=False).save(state, step=70)
    assert m.meta["event_index"] == 6
    mgr2.close()


def test_stale_cohort_records_do_not_block_commit(small_setup, tmp_path):
    """Crash-leftover records from a WIDER participant cohort at the
    same step must not block a narrower retry's commit."""
    model, state, reg = small_setup
    mgr = CheckpointManager(tmp_path, reg,
                            make_policy("full", model.layer_units()),
                            async_save=False)
    # crashed 4-wide attempt: only participants 2 and 3 got to publish
    ShardedSaver(mgr, 2, 4).save_shards(state, step=10)
    ShardedSaver(mgr, 3, 4).save_shards(state, step=10)
    # 2-wide retry at the same step
    ShardedSaver(mgr, 0, 2).save_shards(state, step=10)
    ShardedSaver(mgr, 1, 2).save_shards(state, step=10)
    manifest = ShardCoordinator(mgr).commit(10, 2)
    assert manifest.meta["sharded"]["n_participants"] == 2
    restored = mgr.restore(steps_lib.state_specs(model))
    _assert_state_equal(state, restored)
    mgr.close()


def test_coordinator_rejects_incomplete_cover(small_setup, tmp_path):
    """A shard set with a hole (participant published, but its blocks
    don't tile the unit) must not commit."""
    model, state, reg = small_setup
    mgr = CheckpointManager(tmp_path, reg,
                            make_policy("full", model.layer_units()),
                            async_save=False)
    # Both participants claim the SAME half -> double cover + hole.
    s0 = ShardedSaver(mgr, 0, 2)
    s1 = ShardedSaver(mgr, 1, 2)
    s1.wanted = s0.wanted  # sabotage: duplicate ownership
    s0.save_shards(state, step=10)
    s1.save_shards(state, step=10)
    with pytest.raises(ShardBarrierError, match="do not exactly tile"):
        ShardCoordinator(mgr).commit(10, 2)
    mgr.close()


def test_shard_fallback_is_unit_consistent(small_setup, tmp_path):
    """When one shard of a unit loses its newest object, the WHOLE unit
    falls back to the newest step every shard can serve — a tensor must
    never assemble from mixed manifest steps (a state that never
    existed)."""
    model, state, reg = small_setup
    mgr = CheckpointManager(tmp_path, reg,
                            make_policy("full", model.layer_units()),
                            keep=4, async_save=False)
    ck = ShardedCheckpointer(mgr, 2, parallel=False)
    ck.save(state, step=10)
    drifted = dict(state)
    drifted["params"] = jax.tree.map(
        lambda x: np.asarray(jax.device_get(x))
        + np.ones((), np.asarray(x).dtype),
        state["params"])
    ck.save(drifted, step=20)

    unit = reg.unit_names()[0]
    m20 = mgr.manifests.load(20)
    victim = entry_refs(m20.entries[unit]["weights"])[0]
    assert victim.step == 20  # drift produced a fresh step-20 object
    # simulate storage loss of participant 0's newest weights shard;
    # its delta base (if any) stays, so per-shard fallback WOULD succeed
    mgr.store.object_path(victim.digest).unlink()

    restored = mgr.restore(steps_lib.state_specs(model))
    s = mgr.last_restore_stats
    assert s["fallback_units"].get(f"{unit}/weights") == 10
    # the damaged unit's weights are ENTIRELY step-10 content (both
    # shards aligned), not a mix of step-10 and step-20 halves
    got = reg.extract_unit(restored["params"], unit)
    want = reg.extract_unit(state["params"], unit)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # undamaged units restore at step 20
    other = reg.unit_names()[1]
    for a, b in zip(
            jax.tree.leaves(reg.extract_unit(drifted["params"], other)),
            jax.tree.leaves(reg.extract_unit(restored["params"], other))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


def test_shard_fallback_spans_dedup_steps(small_setup, tmp_path):
    """An unchanged shard's entry dedups to the same digest across
    steps, so one object serves several steps: aligning a unit on an
    older step must succeed when the other shard's content is identical
    at both steps (no false mixed-step error, no data loss)."""
    model, state, reg = small_setup
    mgr = CheckpointManager(tmp_path, reg,
                            make_policy("full", model.layer_units()),
                            keep=4, async_save=False)
    ck = ShardedCheckpointer(mgr, 2, parallel=False)
    ck.save(state, step=10)
    unit = reg.unit_names()[0]

    # drift ONLY participant 1's half (lower axis-0 rows) of one unit's
    # leaves: p0's shard then dedups at step 20 (same digest as step 10)
    def poke_lower(x):
        out = np.asarray(x).copy()
        out[out.shape[0] // 2:] += np.ones((), out.dtype)
        return out

    params = jax.device_get(state["params"])
    drifted = dict(state)
    drifted["params"] = reg.insert_unit(
        params, unit,
        jax.tree.map(poke_lower, reg.extract_unit(params, unit)))
    ck.save(drifted, step=20)

    m20 = mgr.manifests.load(20)
    refs = entry_refs(m20.entries[unit]["weights"])
    by_pid = {r.spec["participant"]: r for r in refs}
    m10 = mgr.manifests.load(10)
    refs10 = {r.spec["participant"]: r
              for r in entry_refs(m10.entries[unit]["weights"])}
    assert by_pid[0].digest == refs10[0].digest, "p0 shard must dedup"
    assert by_pid[1].digest != refs10[1].digest
    mgr.store.object_path(by_pid[1].digest).unlink()

    restored = mgr.restore(steps_lib.state_specs(model))
    # aligned on step 10: the whole unit is step-10 content (p0's half
    # was identical at both steps anyway)
    got = reg.extract_unit(restored["params"], unit)
    for a, b in zip(jax.tree.leaves(reg.extract_unit(params, unit)),
                    jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert mgr.last_restore_stats["fallback_units"].get(
        f"{unit}/weights") == 10
    mgr.close()


def test_shard_fallback_refuses_mixed_step_tensor(small_setup, tmp_path):
    """When NO single manifest step is readable by every shard of a
    unit, restore must fail loudly instead of assembling a tensor that
    never existed."""
    from repro.checkpoint.restore import RestoreError

    model, state, reg = small_setup
    mgr = CheckpointManager(tmp_path, reg,
                            make_policy("full", model.layer_units()),
                            keep=4, async_save=False)
    ck = ShardedCheckpointer(mgr, 2, parallel=False)
    ck.save(state, step=10)
    drifted = dict(state)
    drifted["params"] = jax.tree.map(
        lambda x: np.asarray(jax.device_get(x))
        + np.ones((), np.asarray(x).dtype),
        state["params"])  # every block dirty -> full objects, no deltas
    ck.save(drifted, step=20)

    unit = reg.unit_names()[0]
    p20 = {r.spec["participant"]: r for r in entry_refs(
        mgr.manifests.load(20).entries[unit]["weights"])}
    p10 = {r.spec["participant"]: r for r in entry_refs(
        mgr.manifests.load(10).entries[unit]["weights"])}
    # p1 can only serve step 10, p0 can only serve step 20
    mgr.store.object_path(p20[1].digest).unlink()
    mgr.store.object_path(p10[0].digest).unlink()
    with pytest.raises(RestoreError, match="mixed-step"):
        mgr.restore(steps_lib.state_specs(model))
    mgr.close()


def test_sharded_save_over_legacy_manifest_forces_full(small_setup,
                                                       tmp_path):
    """A pre-content-addressing previous manifest (digest-less refs)
    cannot be carried forward: the sharded event must select every unit
    and commit a fresh, fully-restorable shard manifest."""
    from repro.checkpoint.chunk_store import ChunkRef
    from repro.core.manifest import Manifest

    model, state, reg = small_setup
    mgr = CheckpointManager(tmp_path, reg,
                            make_policy("parity", model.layer_units()),
                            async_save=False)
    ShardedCheckpointer(mgr, 2, parallel=False).save(state, step=10)
    # hack a legacy manifest on top: one unit's ref has no digest
    m = mgr.manifests.load(10)
    unit = reg.unit_names()[0]
    legacy = {u: dict(k) for u, k in m.entries.items()}
    legacy[unit]["weights"] = ChunkRef(
        step=20, unit=unit, kind="weights",
        relpath="step-20/old.chunk", nbytes=0, digest="")
    mgr.manifests.commit(Manifest(step=20, entries=legacy, meta={}))

    mgr2 = CheckpointManager(tmp_path, reg,
                             make_policy("parity", model.layer_units()),
                             async_save=False)
    ck = ShardedCheckpointer(mgr2, 2, parallel=False)
    m30 = ck.save(state, step=30)
    # full selection despite the parity policy, and no digest-less refs
    assert set(m30.saved_units) == set(reg.unit_names())
    assert all(r.digest for kinds in m30.entries.values()
               for e in kinds.values() for r in entry_refs(e))
    restored = mgr2.restore(steps_lib.state_specs(model), step=30)
    _assert_state_equal(state, restored)
    mgr2.close()
    mgr.close()


def test_merge_copies_shard_sets_atomically(small_setup, tmp_path):
    model, state, reg = small_setup
    src = tmp_path / "src"
    mgr = CheckpointManager(src, reg,
                            make_policy("full", model.layer_units()))
    ck = ShardedCheckpointer(mgr, 2)
    ck.save(state, step=10)
    recipe = Recipe(base=CheckpointRef(src, 10),
                    output=tmp_path / "out", select=[])
    stats = merge(recipe, workers=2,
                  stores={str(CheckpointRef(src, 10)): mgr.store})
    mgr.close()
    assert stats["chunks"] > len(reg.units), \
        "sharded entries contribute one copied object per shard"

    mgr2 = CheckpointManager(tmp_path / "out", reg,
                             make_policy("full", model.layer_units()),
                             async_save=False)
    m = mgr2.manifests.load()
    assert all(is_sharded(e) for kinds in m.entries.values()
               for e in kinds.values())
    restored = mgr2.restore(steps_lib.state_specs(model))
    _assert_state_equal(state, restored)
    mgr2.close()


def test_global_save_over_sharded_chain(small_setup, tmp_path):
    """A classic CheckpointManager.save on top of a sharded manifest
    writes fresh global entries (no cross-layout delta) and restores."""
    model, state, reg = small_setup
    mgr = CheckpointManager(tmp_path, reg,
                            make_policy("full", model.layer_units()))
    ShardedCheckpointer(mgr, 2).save(state, step=10)
    mgr.save(state, step=20)
    m = mgr.manifests.load()
    assert not any(is_sharded(e) for kinds in m.entries.values()
                   for e in kinds.values())
    restored = mgr.restore(steps_lib.state_specs(model))
    _assert_state_equal(state, restored)
    mgr.close()


# ------------------------------------------------------------ policy satellite
def _mk_units(n):
    return ([LayerUnit(name=f"block_{i:02d}", path=("blocks",), index=i)
             for i in range(n)]
            + [LayerUnit(name="embed", path=("embed",), kind="aux"),
               LayerUnit(name="final_norm", path=("norm",), kind="aux")])


def test_topk_delta_tie_break_is_deterministic():
    """Equal drift scores must select the FIRST k blocks in registry
    order, independent of the iteration order drift_scores was built in
    (reproducible selections across runs and across the participants of
    one sharded save event)."""
    units = _mk_units(6)
    pol = make_policy("topk_delta", units, frac=0.5)
    blocks = pol.blocks
    tied = {b: 1.0 for b in blocks}
    reversed_insert = {b: 1.0 for b in reversed(blocks)}
    ctx = PolicyContext(event_index=3, step=0, drift_scores=tied)
    ctx_r = PolicyContext(event_index=3, step=0,
                          drift_scores=reversed_insert)
    sel = [u for u in pol.select(ctx) if u.startswith("block")]
    sel_r = [u for u in pol.select(ctx_r) if u.startswith("block")]
    assert sel == sel_r == blocks[:3]
    # partial tie below the cut: the tied tail breaks by block order too
    scores = {b: (2.0 if i == 4 else 1.0) for i, b in enumerate(blocks)}
    sel = [u for u in pol.select(PolicyContext(0, 0, drift_scores=scores))
           if u.startswith("block")]
    assert sel == [blocks[4], blocks[0], blocks[1]]


# ----------------------------------------------------------- mesh subprocess
def _mesh_save_and_resharded_restore(arch):
    code = """
        import tempfile, jax, numpy as np
        from pathlib import Path
        from repro.configs import get_config
        from repro.core import LayerRegistry, make_policy
        from repro.checkpoint.saver import CheckpointManager
        from repro.checkpoint.sharded import (ShardedCheckpointer,
                                              participant_wanted,
                                              combine_states)
        from repro.launch import steps as steps_lib
        from repro.launch.mesh import make_debug_mesh
        from repro.models import build_model

        cfg = get_config(ARCH, reduced=True)
        model = build_model(cfg)
        tmp = Path(tempfile.mkdtemp())
        reg = LayerRegistry(model)
        mesh_save = make_debug_mesh(1, 8)
        sh = steps_lib.state_shardings(model, mesh_save)
        state = steps_lib.init_state(model, jax.random.key(0))
        state = jax.tree.map(jax.device_put, state, sh)
        mgr = CheckpointManager(tmp, reg,
                                make_policy("full", model.layer_units()))
        ShardedCheckpointer(mgr, 2, shardings=sh).save(state, step=7)
        like = steps_lib.state_specs(model)
        mgr.restore(like)
        full_bytes = mgr.last_restore_stats["bytes_read"]
        mgr.close()

        mesh_r = make_debug_mesh(2, 4)
        sh_r = steps_lib.state_shardings(model, mesh_r)
        mgr2 = CheckpointManager(tmp, reg,
                                 make_policy("full", model.layer_units()),
                                 async_save=False)
        results, wanteds = [], []
        for pid in range(4):
            w = participant_wanted(reg, pid, 4, shardings=sh_r)
            results.append(mgr2.restore(like, shardings=sh_r, owned=w))
            s = mgr2.last_restore_stats
            assert s["bytes_read"] < full_bytes, (s["bytes_read"],
                                                  full_bytes)
            assert s["shards_skipped"] > 0
            wanteds.append(w)
        mgr2.close()
        comb = combine_states(like, reg, results, wanteds)
        for key in ("params", "opt"):
            for a, b in zip(jax.tree.leaves(state[key]),
                            jax.tree.leaves(comb[key])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(comb["step"]) == 7
        print("OK")
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    code = textwrap.dedent(code).replace("ARCH", repr(arch))
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"


def test_mesh_sharded_save_and_resharded_restore():
    """Acceptance: save on a 1x8 mesh with 2 participants, restore on a
    2x4 mesh as 4 participants — bit-exact after stitching, and every
    restore participant reads strictly fewer bytes than the full-array
    restore of the same manifest."""
    _mesh_save_and_resharded_restore("llama3.2-3b")


def test_mesh_resharded_restore_with_unowned_leaves():
    """The same round trip on the state-space model, where a restore
    participant owns no slice at all of some small leaves (a replicated
    per-layer norm goes to one owner): those leaves restore as zeros on
    that participant instead of breaking the unit's assembly."""
    _mesh_save_and_resharded_restore("mamba2-370m")

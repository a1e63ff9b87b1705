"""Incremental checkpoint subsystem: content-addressed dedup, sparse-XOR
delta encoding, refcounted GC, and their end-to-end composition through
CheckpointManager and the explicit merge engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proptest import cases, rand_shape

from repro.checkpoint import ChunkStore
from repro.checkpoint import compression
from repro.checkpoint.chunk_store import content_digest
from repro.checkpoint import saver
from repro.checkpoint.saver import CheckpointManager
from repro.configs import get_config
from repro.core import (
    CheckpointRef,
    LayerRegistry,
    ManifestStore,
    Recipe,
    SelectRule,
    make_policy,
    merge,
)
from repro.kernels.block_fp import LeafFP
from repro.launch import steps as steps_lib
from repro.models import build_model


# -------------------------------------------------------------- delta codec
def test_delta_codec_roundtrip_property():
    def gen(rs):
        base = rs.bytes(int(rs.randint(1, 5000)))
        cur = bytearray(base)
        # random sparse mutations, possibly resizing
        for _ in range(rs.randint(0, 8)):
            if cur:
                cur[rs.randint(0, len(cur))] ^= 1 + rs.randint(0, 255)
        if rs.rand() < 0.3:
            cur += rs.bytes(int(rs.randint(0, 100)))
        elif rs.rand() < 0.3 and len(cur) > 1:
            del cur[len(cur) // 2:]
        return bytes(cur), base

    for cur, base in cases(24, gen):
        blob = compression.delta_encode(cur, base)
        assert compression.is_delta(blob)
        assert compression.delta_decode(blob, base) == cur


def test_delta_codec_sparse_change_is_small():
    base = bytes(100_000)
    cur = bytearray(base)
    cur[5000:5010] = b"0123456789"
    blob = compression.delta_encode(bytes(cur), base)
    assert len(blob) < 200  # one tiny segment, not 100 KB
    assert compression.delta_decode(blob, base) == bytes(cur)


def test_delta_codec_identical_payloads():
    base = np.random.RandomState(0).bytes(4096)
    blob = compression.delta_encode(base, base)
    assert compression.delta_decode(blob, base) == base
    assert len(blob) < 64


# ------------------------------------------------------- store-level dedup
def test_same_payload_twice_one_object_refcount_two(tmp_path):
    store = ChunkStore(tmp_path)
    tree = {"w": np.arange(4096, dtype=np.float32).reshape(64, 64)}
    r1 = store.write(10, "block_000", "weights", tree)
    r2 = store.write(20, "block_000", "weights", tree)
    # same content => same digest, ONE object on disk, second write free
    assert r1.digest == r2.digest
    assert len(list((tmp_path / "objects").glob("*/*.chunk"))) == 1
    assert store.stats["dedup_hits"] == 1
    assert store.stats["full_chunks"] == 1
    # two manifests would each hold a reference
    store.incref([r1.digest])
    store.incref([r2.digest])
    assert store.refcount(r1.digest) == 2
    # refs differ only in provenance, not content
    assert (r1.step, r2.step) == (10, 20)
    assert r1.relpath == r2.relpath


def test_dedup_is_unit_independent(tmp_path):
    """Two different units with identical tensors share one object."""
    store = ChunkStore(tmp_path)
    tree = {"w": np.ones((32, 32), np.float32)}
    r1 = store.write(1, "block_000", "weights", tree)
    r2 = store.write(1, "block_007", "weights", tree)
    assert r1.digest == r2.digest
    assert len(list((tmp_path / "objects").glob("*/*.chunk"))) == 1


# ------------------------------------------------------- store-level delta
def test_delta_chunk_roundtrip_byte_identical(tmp_path):
    store = ChunkStore(tmp_path)
    rs = np.random.RandomState(3)
    base_tree = {"w": rs.standard_normal((128, 64)).astype(np.float32),
                 "b": rs.standard_normal(64).astype(np.float32)}
    r_full = store.write(1, "u", "weights", base_tree)
    assert r_full.stored == "full"

    cur_tree = {"w": base_tree["w"].copy(), "b": base_tree["b"].copy()}
    cur_tree["w"][3, :5] += 1.0  # sparse drift
    r_delta = store.write(2, "u", "weights", cur_tree,
                          delta_base=r_full.digest)
    assert r_delta.stored == "delta"
    assert r_delta.delta_base == r_full.digest
    assert r_delta.nbytes < r_full.nbytes / 4

    out, _ = store.read(r_delta)
    np.testing.assert_array_equal(out["w"], cur_tree["w"])
    np.testing.assert_array_equal(out["b"], cur_tree["b"])
    # canonical payload reconstructs bit-exactly => digest verifies
    assert content_digest(store.read_canonical(r_delta.digest)) \
        == r_delta.digest


def test_delta_chain_stays_depth_one_and_rebases(tmp_path):
    """Successive deltas all point at the same FULL object, and after
    rebase_every consecutive deltas the store forces a full rebase."""
    store = ChunkStore(tmp_path, rebase_every=4)
    tree = {"w": np.zeros((256,), np.float32)}
    refs = [store.write(0, "u", "weights", tree)]
    for i in range(1, 6):
        tree = {"w": tree["w"].copy()}
        tree["w"][i] = float(i)
        refs.append(store.write(i, "u", "weights", tree,
                                delta_base=refs[-1].digest))
    assert refs[0].stored == "full"
    for r in refs[1:5]:
        assert r.stored == "delta"
        assert r.delta_base == refs[0].digest  # never a delta-of-delta
    # 5th consecutive delta candidate is forced full: one base object must
    # not underpin an unbounded run of checkpoints
    assert refs[5].stored == "full"
    out, _ = store.read(refs[-1])
    np.testing.assert_array_equal(out["w"], tree["w"])
    # the rebased full becomes the next chain's base
    tree2 = {"w": tree["w"].copy()}
    tree2["w"][7] = 7.0
    r = store.write(6, "u", "weights", tree2, delta_base=refs[5].digest)
    assert r.stored == "delta" and r.delta_base == refs[5].digest


def test_dense_change_falls_back_to_full(tmp_path):
    """When every byte drifts, a delta cannot win; the store rebases."""
    store = ChunkStore(tmp_path)
    rs = np.random.RandomState(7)
    t1 = {"w": rs.standard_normal((64, 64)).astype(np.float32)}
    r1 = store.write(1, "u", "weights", t1)
    t2 = {"w": (t1["w"] * 1.7).astype(np.float32)}
    r2 = store.write(2, "u", "weights", t2, delta_base=r1.digest)
    assert r2.stored == "full"
    assert r2.delta_base is None


def test_lossy_codec_never_delta_encodes(tmp_path):
    store = ChunkStore(tmp_path, codec="int8")
    rs = np.random.RandomState(9)
    t1 = {"w": rs.standard_normal((512, 8)).astype(np.float32)}
    r1 = store.write(1, "u", "weights", t1)
    t2 = {"w": t1["w"].copy()}
    t2["w"][0, 0] += 1.0
    r2 = store.write(2, "u", "weights", t2, delta_base=r1.digest)
    assert r2.stored == "full"


# ---------------------------------------------------------------------- gc
def test_gc_frees_only_unreferenced_digests(tmp_path):
    store = ChunkStore(tmp_path)
    shared = store.write(1, "a", "weights", {"w": np.ones(64, np.float32)})
    only1 = store.write(1, "b", "weights", {"w": np.full(64, 2.0, np.float32)})
    only2 = store.write(2, "b", "weights", {"w": np.full(64, 3.0, np.float32)})
    # manifest 1 refs {shared, only1}; manifest 2 refs {shared, only2}
    store.incref([shared.digest, only1.digest])
    store.incref([shared.digest, only2.digest])
    assert store.gc_objects() == 0  # everything referenced

    # drop manifest 1
    store.decref([shared.digest, only1.digest])
    freed = store.gc_objects()
    assert freed == only1.nbytes
    assert not store.has(only1.digest)
    assert store.has(shared.digest) and store.has(only2.digest)
    assert store.refcount(shared.digest) == 1


def test_gc_keeps_delta_base_alive(tmp_path):
    """A full object outlives its own manifest while a delta needs it."""
    store = ChunkStore(tmp_path)
    t1 = {"w": np.zeros(1024, np.float32)}
    r1 = store.write(1, "u", "weights", t1)
    t2 = {"w": t1["w"].copy()}
    t2["w"][0] = 1.0
    r2 = store.write(2, "u", "weights", t2, delta_base=r1.digest)
    assert r2.stored == "delta"
    # manifest 1: {r1}; manifest 2: {r2 + its base r1}
    store.incref([r1.digest])
    store.incref([r2.digest, r2.delta_base])
    store.decref([r1.digest])  # manifest 1 dropped
    assert store.gc_objects() == 0
    assert store.has(r1.digest)  # pinned by the delta
    out, _ = store.read(r2)
    np.testing.assert_array_equal(out["w"], t2["w"])
    # dropping manifest 2 releases both
    store.decref([r2.digest, r2.delta_base])
    assert store.gc_objects() > 0
    assert not store.has(r1.digest) and not store.has(r2.digest)


def test_gc_sweeps_orphans(tmp_path):
    """Objects never referenced by a manifest (crash mid-save) are swept."""
    store = ChunkStore(tmp_path)
    ref = store.write(1, "u", "weights", {"w": np.ones(16, np.float32)})
    assert store.gc_objects() == ref.nbytes
    assert not store.has(ref.digest)


def test_gc_sweeps_stale_tmp_files(tmp_path):
    """Crash-leftover _atomic_write tmp files are reclaimed by gc."""
    store = ChunkStore(tmp_path)
    ref = store.write(1, "u", "weights", {"w": np.ones(16, np.float32)})
    store.incref([ref.digest])
    stale = store.object_path(ref.digest).with_suffix(".chunk.tmp-dead-1")
    stale.write_bytes(b"x" * 100)
    assert store.gc_objects() == 100
    assert not stale.exists() and store.has(ref.digest)


def test_concurrent_identical_writes_dedup(tmp_path):
    """Writer threads persisting bitwise-identical units produce one write
    plus dedup hits — not duplicated objects or double-counted stats."""
    from repro.checkpoint import AsyncWriter
    store = ChunkStore(tmp_path)
    w = AsyncWriter(num_threads=4)
    tree = {"w": np.random.RandomState(0)
            .standard_normal((128, 128)).astype(np.float32)}
    pends = [w.submit(store.write, i, f"u{i}", "weights", tree)
             for i in range(16)]
    w.drain()
    w.close()
    refs = [p.result() for p in pends]
    assert len({r.digest for r in refs}) == 1
    assert len(list((tmp_path / "objects").glob("*/*.chunk"))) == 1
    assert store.stats["full_chunks"] == 1
    assert store.stats["dedup_hits"] == 15


# ------------------------------------------------------------ manager-level
@pytest.fixture(scope="module")
def small_setup():
    cfg = get_config("llama3.2-3b", reduced=True)
    model = build_model(cfg)
    state = steps_lib.init_state(model, jax.random.key(0))
    registry = LayerRegistry(model)
    return model, state, registry


def test_resave_unchanged_state_writes_nothing(tmp_path, small_setup):
    """ISSUE acceptance: second FullPolicy save of the same state is ~0
    new bytes — every chunk dedups against the first event."""
    model, state, registry = small_setup
    mgr = CheckpointManager(tmp_path, registry,
                            make_policy("full", model.layer_units()),
                            async_save=False)
    mgr.save(state, step=10)
    first_written = mgr.last_save_stats["written_bytes"]
    assert first_written > 0
    usage1 = mgr.disk_usage()

    m2 = mgr.save(state, step=20)
    s = mgr.last_save_stats
    assert s["written_bytes"] == 0
    assert s["full_chunks"] == 0 and s["delta_chunks"] == 0
    assert s["dedup_hits"] == 2 * len(registry.unit_names())  # w + opt each
    assert mgr.disk_usage()["total"] == usage1["total"]
    # both manifests reference the same objects -> refcount 2
    d = m2.entries["block_000"]["weights"].digest
    assert mgr.store.refcount(d) == 2
    # restore from the deduped manifest is still bitwise exact
    restored = mgr.restore(steps_lib.state_specs(model))
    for a, b in zip(jax.tree.leaves(state["params"]),
                    jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


def _sparse_drift(registry, state, unit):
    """Change a handful of elements in one block (delta-favourable)."""
    w = registry.extract_unit(state["params"], unit)
    leaves, treedef = jax.tree.flatten(w)
    a = np.asarray(leaves[0]).copy()
    a.reshape(-1)[:8] += np.asarray(1.0, a.dtype)
    leaves[0] = a
    return dict(state, params=registry.insert_unit(
        state["params"], unit, jax.tree.unflatten(treedef, leaves)))


def test_delta_manifest_restore_equals_full_restore(tmp_path, small_setup):
    """ISSUE acceptance: restore from a delta-encoded manifest is
    byte-identical to restore from a store with deltas disabled."""
    model, state, registry = small_setup
    state2 = _sparse_drift(registry, state, "block_001")

    restored = {}
    for name, delta in (("delta", True), ("plain", False)):
        mgr = CheckpointManager(tmp_path / name, registry,
                                make_policy("full", model.layer_units()),
                                async_save=False, delta=delta)
        mgr.save(state, step=10)
        m = mgr.save(state2, step=20)
        ref = m.entries["block_001"]["weights"]
        assert ref.stored == ("delta" if delta else "full")
        restored[name] = mgr.restore(steps_lib.state_specs(model))
        mgr.close()

    for a, b in zip(jax.tree.leaves(restored["delta"]),
                    jax.tree.leaves(restored["plain"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and both equal the source state bitwise
    for a, b in zip(jax.tree.leaves(restored["delta"]["params"]),
                    jax.tree.leaves(state2["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _tables_per_leaf(cur):
    """Reference fetch: a blocking ``device_get`` of each leaf's vectors."""
    return [LeafFP(path=l.path, shape=l.shape, dtype=l.dtype,
                   nbytes=l.nbytes, block_bytes=l.block_bytes,
                   fp=np.asarray(jax.device_get(l.fp)),
                   sumsq=np.asarray(jax.device_get(l.sumsq)))
            for l in cur]


def test_one_transfer_tables_store_the_same_bytes(tmp_path, small_setup,
                                                  monkeypatch):
    """Two events (every unit in full, then one unit as a block delta)
    commit the same manifests and objects whether the fingerprint tables
    cross in one transfer per unit or one fetch per leaf vector."""
    model, state, registry = small_setup
    state2 = _sparse_drift(registry, state, "block_001")
    saved = {}
    for name in ("one_transfer", "per_leaf"):
        if name == "per_leaf":
            monkeypatch.setattr(saver, "tables_to_host", _tables_per_leaf)
        mgr = CheckpointManager(tmp_path / name, registry,
                                make_policy("full", model.layer_units()),
                                async_save=False, fp_block_bytes=4096)
        mgr.save(state, step=10)
        m = mgr.save(state2, step=20)
        assert m.entries["block_001"]["weights"].stored == "delta"
        saved[name] = ([mgr.manifests.load(s).to_json() for s in (10, 20)],
                       set(mgr.store.iter_digests()))
        mgr.close()
    assert saved["one_transfer"] == saved["per_leaf"]


def test_manager_gc_drops_only_unshared_objects(tmp_path, small_setup):
    model, state, registry = small_setup
    mgr = CheckpointManager(tmp_path, registry,
                            make_policy("full", model.layer_units()),
                            async_save=False, keep=2)
    st = state
    for step in (10, 20, 30):
        st = _sparse_drift(registry, st, "block_000")
        mgr.save(st, step=step)
    assert mgr.manifests.all_steps() == [20, 30]
    # opt chunks never changed: shared across all events, still present
    opt_digest = mgr.manifests.load(30).entries["block_000"]["opt"].digest
    assert mgr.store.refcount(opt_digest) == 2
    # every object on disk is referenced by a retained manifest
    referenced = set()
    for s in (20, 30):
        referenced |= set(mgr.manifests.load(s).referenced_digests())
    assert set(mgr.store.iter_digests()) == referenced
    mgr.close()


def test_resave_same_step_does_not_leak_refcounts(tmp_path, small_setup):
    """Overwriting a step's manifest releases the replaced references."""
    model, state, registry = small_setup
    mgr = CheckpointManager(tmp_path, registry,
                            make_policy("full", model.layer_units()),
                            async_save=False)
    m = mgr.save(state, step=10)
    d = m.entries["block_000"]["weights"].digest
    assert mgr.store.refcount(d) == 1
    mgr.save(state, step=10)  # same step, same content: manifest replaced
    assert mgr.store.refcount(d) == 1  # not 2 — the old manifest is gone
    # replacing with drifted content: the old object keeps exactly the
    # references the new manifest still holds (delta base or nothing)
    state2 = _sparse_drift(registry, state, "block_000")
    m3 = mgr.save(state2, step=10)
    new_ref = m3.entries["block_000"]["weights"]
    assert new_ref.digest != d
    expected = 1 if new_ref.delta_base == d else 0
    assert mgr.store.refcount(d) == expected
    mgr.close()


def test_delta_run_survives_reopen(tmp_path, small_setup):
    """The rebase_every bound replays from the manifest chain: a restart
    must not reset the consecutive-delta counter (else one full base could
    underpin the whole retention window across crash loops)."""
    model, state, registry = small_setup
    def mk():
        return CheckpointManager(tmp_path, registry,
                                 make_policy("full", model.layer_units()),
                                 async_save=False, keep=16)
    mgr = mk()
    st = state
    mgr.save(st, step=0)
    for step in (1, 2):
        st = _sparse_drift(registry, st, "block_001")
        m = mgr.save(st, step=step)
        assert m.entries["block_001"]["weights"].stored == "delta"
    mgr.close()

    mgr2 = mk()  # "restart": counter must resume at 2, not 0
    for step in (3, 4):
        st = _sparse_drift(registry, st, "block_001")
        m = mgr2.save(st, step=step)
        assert m.entries["block_001"]["weights"].stored == "delta"
    st = _sparse_drift(registry, st, "block_001")
    m = mgr2.save(st, step=5)  # 5th consecutive delta candidate -> rebase
    assert m.entries["block_001"]["weights"].stored == "full"
    mgr2.close()


def test_refcounts_rebuild_across_reopen(tmp_path, small_setup):
    """A fresh manager derives refcounts from manifests (nothing persisted
    beyond the manifests themselves)."""
    model, state, registry = small_setup
    mgr = CheckpointManager(tmp_path, registry,
                            make_policy("full", model.layer_units()),
                            async_save=False)
    m1 = mgr.save(state, step=10)
    mgr.save(state, step=20)
    mgr.close()

    mgr2 = CheckpointManager(tmp_path, registry,
                             make_policy("full", model.layer_units()),
                             async_save=False, keep=1)
    d = m1.entries["block_000"]["weights"].digest
    assert mgr2.store.refcount(d) == 2
    restored = mgr2.restore(steps_lib.state_specs(model))
    assert int(restored["step"]) == 20
    mgr2.close()


def test_merge_shares_objects_across_sources(tmp_path, small_setup):
    """Digest-level merge copy: units with identical content (within or
    across sources) land as ONE object in the output store."""
    model, state, registry = small_setup
    # make block_001 and block_003 byte-identical: their chunks share a
    # digest, so the merge must copy the object exactly once
    state = dict(state, params=registry.insert_unit(
        state["params"], "block_003",
        registry.extract_unit(state["params"], "block_001")))
    pol = make_policy("full", model.layer_units())
    mgr = CheckpointManager(tmp_path / "ck", registry, pol, async_save=False)
    mgr.save(state, step=100)
    state2 = _sparse_drift(registry, state, "block_000")
    mgr.save(state2, step=200)

    recipe = Recipe(
        base=CheckpointRef(tmp_path / "ck", 200),
        output=tmp_path / "merged",
        select=[SelectRule(units=["block_001", "embed"],
                           source=CheckpointRef(tmp_path / "ck", 100))])
    stats = merge(recipe, workers=2)
    # block_001@100 and block_003@200 carry the same digest
    assert stats["shared_chunks"] > 0

    out_m = ManifestStore(tmp_path / "merged").load(200)
    assert out_m.entries["block_001"]["weights"].digest == \
        out_m.entries["block_003"]["weights"].digest
    out_files = {f.stem
                 for f in (tmp_path / "merged" / "objects").glob("*/*.chunk")}
    assert out_m.entries["block_001"]["weights"].digest in out_files
    src_m = mgr.manifests.load(200)
    assert out_m.entries["block_001"]["weights"].digest == \
        src_m.entries["block_001"]["weights"].digest
    # merged output restores bitwise to the mixed state
    mgr2 = CheckpointManager(tmp_path / "merged", registry, pol,
                             async_save=False)
    got = mgr2.restore(steps_lib.state_specs(model))
    exp = registry.extract_unit(state2["params"], "block_000")
    for a, b in zip(jax.tree.leaves(exp),
                    jax.tree.leaves(registry.extract_unit(got["params"],
                                                          "block_000"))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()
    mgr2.close()


def test_merge_copies_delta_base_transitively(tmp_path, small_setup):
    """A delta-encoded unit merges correctly: its full base object rides
    along and the output restores byte-exactly."""
    model, state, registry = small_setup
    pol = make_policy("full", model.layer_units())
    mgr = CheckpointManager(tmp_path / "ck", registry, pol, async_save=False)
    mgr.save(state, step=100)
    state2 = _sparse_drift(registry, state, "block_002")
    m2 = mgr.save(state2, step=200)
    ref = m2.entries["block_002"]["weights"]
    assert ref.stored == "delta"

    recipe = Recipe(base=CheckpointRef(tmp_path / "ck", 200),
                    output=tmp_path / "merged", select=[])
    merge(recipe, workers=2)
    out_store = ChunkStore(tmp_path / "merged")
    assert out_store.has(ref.digest) and out_store.has(ref.delta_base)
    tree, _ = out_store.read_digest(ref.digest)
    exp = registry.extract_unit(state2["params"], "block_002")
    for a, b in zip(jax.tree.leaves(exp), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()

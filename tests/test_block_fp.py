"""Block fingerprint pipeline: kernel-vs-oracle property sweeps, the
block-sparse delta v2 format, zero-D2H unchanged re-saves, restart
recovery, the one-transfer host tables, and the AsyncWriter
wait()/close semantics."""
import dataclasses
import os
import subprocess
import sys
import textwrap
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proptest import cases

from repro.checkpoint import AsyncWriteError, AsyncWriter
from repro.checkpoint import compression
from repro.checkpoint import fingerprint as fputil
from repro.checkpoint.saver import CheckpointManager
from repro.configs import get_config
from repro.core import DeltaTracker, LayerRegistry, make_policy
from repro.kernels.block_fp import (
    block_fingerprint,
    dirty_block_indices,
    fingerprint_array,
    fingerprint_tree,
    gather_blocks,
    leaves_match,
    tree_to_host,
)
from repro.launch import steps as steps_lib
from repro.models import build_model

BB = 4096  # small blocks so reduced-model leaves span many of them


# ------------------------------------------------------------ kernel vs ref
@pytest.mark.parametrize("dtype,shape", [
    (jnp.float32, (1000,)),
    (jnp.bfloat16, (300, 7)),          # non-block-multiple, 2-byte dtype
    (jnp.float32, (4, 33, 9)),         # stacked-unit-like 3D, ragged
    (jnp.int32, (64, 64)),
    (jnp.float16, (123,)),
    (jnp.bfloat16, (8, 2048)),         # exact block multiple
])
def test_kernel_matches_oracle(dtype, shape):
    x = jax.random.normal(jax.random.PRNGKey(sum(shape)), shape)
    x = (x * 100).astype(dtype)
    for bb in (1024, 65536):
        fp, ss = block_fingerprint(x, block_bytes=bb, interpret=True)
        ref = fingerprint_array(np.asarray(x), bb)
        assert np.array_equal(np.asarray(fp), ref.fp)
        np.testing.assert_allclose(np.asarray(ss), ref.sumsq, rtol=1e-4)


def test_kernel_property_sweep():
    def gen(rs):
        dtype = rs.choice(["float32", "bfloat16"])
        ndim = int(rs.randint(1, 4))
        shape = tuple(int(rs.randint(1, 40)) for _ in range(ndim))
        return dtype, shape, int(rs.choice([256, 1024]))

    for dtype, shape, bb in cases(10, gen):
        a = np.random.RandomState(len(shape)).standard_normal(shape)
        x = jnp.asarray(a, dtype=dtype)
        fp, _ = block_fingerprint(x, block_bytes=bb, interpret=True)
        ref = fingerprint_array(np.asarray(x), bb)
        assert np.array_equal(np.asarray(fp), ref.fp), (dtype, shape, bb)


def test_fingerprint_localizes_dirty_blocks():
    rs = np.random.RandomState(0)
    a = rs.standard_normal(8 * 1024).astype(np.float32)  # 32 KiB, 8 blocks
    b = a.copy()
    b[5 * 1024 + 3] += 1.0  # dirty exactly block 5
    ca = fingerprint_array(a, BB)
    cb = fingerprint_array(b, BB)
    assert list(dirty_block_indices(cb, ca)) == [5]
    # gather moves exactly that block, with the changed value in place
    g = np.asarray(gather_blocks(jnp.asarray(b), np.array([5]),
                                 block_bytes=BB))
    assert g.shape == (1, BB // 4)
    np.testing.assert_array_equal(g[0], b[5 * 1024:6 * 1024])


def test_tree_fingerprint_roundtrip_and_match():
    tree = {"w": jnp.arange(3000, dtype=jnp.float32),
            "b": {"c": jnp.ones((17, 5), jnp.bfloat16)}}
    cur = fingerprint_tree(tree, block_bytes=BB, interpret=True)
    assert leaves_match(cur, cur)
    # a host table packed/unpacked through the envelope format still matches
    table = fputil.pack_table(tree_to_host(cur))
    assert leaves_match(cur, fputil.unpack_table(table))
    # digest is content-derived and sensitive to any leaf change
    tree2 = {"w": tree["w"].at[0].add(1), "b": tree["b"]}
    cur2 = fingerprint_tree(tree2, block_bytes=BB, interpret=True)
    assert not leaves_match(cur2, cur)
    t2 = fputil.pack_table(tree_to_host(cur2))
    assert fputil.fp_digest(t2) != fputil.fp_digest(table)


# ------------------------------------------------- one-transfer host tables
def _mixed_tree(devices):
    """bf16, f32, int32 and bool leaves, a scalar, a leaf smaller than
    one block and leaves with a partial tail block, placed on
    ``devices`` in turn (one device group per device)."""
    rs = np.random.RandomState(7)
    leaves = {
        "a_bf16": jnp.asarray(rs.standard_normal((40, 70)), jnp.bfloat16),
        "b_f32": jnp.asarray(rs.standard_normal((3, 1024)), jnp.float32),
        "c_i32": jnp.asarray(rs.randint(-2**31, 2**31 - 1, (100,)),
                             jnp.int32),
        "d_bool": jnp.asarray(rs.rand(5000) > 0.5),
        "e_scalar": jnp.float32(3.25),
        "f_f32": jnp.asarray(rs.standard_normal((2, 1500)), jnp.float32),
    }
    return {k: jax.device_put(v, devices[i % len(devices)])
            for i, (k, v) in enumerate(sorted(leaves.items()))}


def check_one_transfer_tables(n_devices: int, source: str) -> None:
    """``tree_to_host`` makes one ``device_get`` and gives the same
    tables, bit for bit, as a ``device_get`` of each leaf's vector.
    ``source="table"`` fetches ``fingerprint_tree``'s concatenated
    per-group tables; ``"per_leaf"`` drops them, as the fused gather's
    outputs come, so each leaf's ``fp`` and ``sumsq`` are copied."""
    devices = jax.devices()[:n_devices]
    assert len(devices) == n_devices
    cur = fingerprint_tree(_mixed_tree(devices), block_bytes=BB)
    assert len({id(l.table[0]) for l in cur}) == n_devices
    if source == "per_leaf":
        cur = [dataclasses.replace(l, table=None) for l in cur]
    calls = []
    device_get = jax.device_get

    def counted(x):
        calls.append(x)
        return device_get(x)

    jax.device_get = counted
    try:
        host = tree_to_host(cur)
    finally:
        jax.device_get = device_get
    assert len(calls) == 1

    ref = [dataclasses.replace(l, fp=np.asarray(jax.device_get(l.fp)),
                               sumsq=None, table=None) for l in cur]
    assert [l.n_blocks for l in ref] == [2, 3, 1, 2, 1, 3]
    for h, r, c in zip(host, ref, cur):
        assert h.meta_matches(r) and h.table is None
        assert isinstance(h.fp, np.ndarray) and h.fp.dtype == np.uint32
        assert h.fp.shape == r.fp.shape and h.fp.tobytes() == r.fp.tobytes()
        if source == "table":
            assert h.sumsq is c.sumsq  # advisory: left on the device
        else:
            assert isinstance(h.sumsq, np.ndarray)
            assert h.sumsq.tobytes() == np.asarray(
                jax.device_get(c.sumsq)).tobytes()
    assert (fputil.fp_digest(fputil.pack_table(host))
            == fputil.fp_digest(fputil.pack_table(ref)))


@pytest.mark.parametrize("n_devices,source", [
    (1, "table"), (1, "per_leaf"), (2, "table")],
    ids=["one_group", "per_leaf_vectors", "two_groups"])
def test_one_transfer_tables_match_per_leaf_fetch(n_devices, source):
    if n_devices <= len(jax.devices()):
        check_one_transfer_tables(n_devices, source)
        return
    # jax fixes the device count at start-up: two CPU devices take a
    # process of their own
    tests = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{n_devices}",
               PYTHONPATH=os.pathsep.join([str(tests.parent / "src"),
                                           str(tests)]))
    code = f"""
        import test_block_fp
        test_block_fp.check_one_transfer_tables({n_devices}, {source!r})
        print("OK")
    """
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0 and "OK" in out.stdout, (
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}")


# ------------------------------------------------------- block delta format
def test_block_delta_codec_roundtrip():
    rec = {"name": "w", "shape": [100], "dtype": "float32", "nbytes": 400,
           "block": 64, "idx": [1, 3], "data": bytes(range(64)) * 2}
    blob = compression.block_delta_encode([rec], compress="none")
    assert compression.is_block_delta(blob)
    out = compression.block_delta_decode(blob)
    assert out[0]["idx"] == [1, 3] and out[0]["data"] == rec["data"]


@pytest.fixture(scope="module")
def small_setup():
    cfg = get_config("llama3.2-3b", reduced=True)
    model = build_model(cfg)
    state = steps_lib.init_state(model, jax.random.key(0))
    registry = LayerRegistry(model)
    return model, state, registry


def _drift_unit(registry, state, unit, n=10):
    sub = registry.extract_unit(state["params"], unit)
    leaves, treedef = jax.tree.flatten(sub)
    a = np.asarray(leaves[0]).copy()
    a.flat[:n] += 1
    leaves[0] = jnp.asarray(a)
    return dict(state, params=registry.insert_unit(
        state["params"], unit, jax.tree.unflatten(treedef, leaves)))


def test_block_sparse_delta_restores_bitwise(tmp_path, small_setup):
    model, state, registry = small_setup
    mgr = CheckpointManager(tmp_path, registry,
                            make_policy("full", model.layer_units()),
                            async_save=False, fp_block_bytes=BB)
    mgr.save(state, step=10)
    state2 = _drift_unit(registry, state, "block_001")
    mgr.save(state2, step=20)
    s = mgr.last_save_stats
    assert s["delta_chunks"] == 1          # only the drifted unit rewrote
    assert 0 < s["d2h_bytes"] < s["logical_bytes"] / 10
    restored = mgr.restore(steps_lib.state_specs(model))
    for key in ("params", "opt"):
        for a, b in zip(jax.tree.leaves(state2[key]),
                        jax.tree.leaves(restored[key])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


def test_unchanged_resave_zero_d2h(tmp_path, small_setup):
    """Acceptance: a re-save of unchanged content transfers ZERO payload
    bytes device->host and hashes zero payload bytes."""
    model, state, registry = small_setup
    mgr = CheckpointManager(tmp_path, registry,
                            make_policy("full", model.layer_units()),
                            async_save=True, fp_block_bytes=BB)
    mgr.save(state, step=10)
    assert mgr.last_save_stats["d2h_bytes"] > 0  # first event is full
    mgr.save(state, step=20)
    s = mgr.last_save_stats
    assert s["d2h_bytes"] == 0
    assert s["hashed_bytes"] == 0
    assert s["written_bytes"] == 0
    assert s["dirty_block_frac"] == 0.0
    assert s["dedup_hits"] == 2 * len(registry.units)
    # the dedup'd manifest still restores bitwise
    restored = mgr.restore(steps_lib.state_specs(model))
    for a, b in zip(jax.tree.leaves(state["params"]),
                    jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


def test_restart_recovers_fingerprints(tmp_path, small_setup):
    """After a process restart the reference vectors reload from the object
    envelopes: an unchanged re-save is still zero-D2H."""
    model, state, registry = small_setup
    pol = make_policy("full", model.layer_units())
    mgr = CheckpointManager(tmp_path, registry, pol, async_save=False,
                            fp_block_bytes=BB)
    mgr.save(state, step=10)
    mgr.close()
    mgr2 = CheckpointManager(tmp_path, registry, pol, async_save=False,
                             fp_block_bytes=BB)
    mgr2.save(state, step=20)
    assert mgr2.last_save_stats["d2h_bytes"] == 0
    mgr2.close()


def test_v1_xor_chunks_still_read(tmp_path, small_setup):
    """Legacy path compatibility: objects written without fingerprinting
    (canonical digests, XOR deltas) read back alongside v2 objects."""
    model, state, registry = small_setup
    pol = make_policy("full", model.layer_units())
    legacy = CheckpointManager(tmp_path, registry, pol, async_save=False,
                               fingerprint=False)
    legacy.save(state, step=10)
    state2 = _drift_unit(registry, state, "block_000")
    legacy.save(state2, step=20)
    assert legacy.last_save_stats["delta_chunks"] > 0  # wrote XOR deltas
    legacy.close()
    # a fingerprinting manager on the same root restores the v1 chain...
    mgr = CheckpointManager(tmp_path, registry, pol, async_save=False,
                            fp_block_bytes=BB)
    restored = mgr.restore(steps_lib.state_specs(model))
    for a, b in zip(jax.tree.leaves(state2["params"]),
                    jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # ...and writes v2 objects on top of it without disturbing v1 reads
    state3 = _drift_unit(registry, state2, "block_001")
    mgr.save(state3, step=30)
    restored3 = mgr.restore(steps_lib.state_specs(model))
    for a, b in zip(jax.tree.leaves(state3["params"]),
                    jax.tree.leaves(restored3["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


def test_corrupt_block_delta_falls_back(tmp_path, small_setup):
    model, state, registry = small_setup
    mgr = CheckpointManager(tmp_path, registry,
                            make_policy("full", model.layer_units()),
                            async_save=False, fp_block_bytes=BB)
    mgr.save(state, step=10)
    state2 = _drift_unit(registry, state, "block_000")
    mgr.save(state2, step=20)
    m2 = mgr.manifests.load(20)
    victim = tmp_path / m2.entries["block_000"]["weights"].relpath
    raw = bytearray(victim.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    victim.write_bytes(bytes(raw))
    restored = mgr.restore(steps_lib.state_specs(model))
    # block_000 fell back to its step-10 content
    exp = registry.extract_unit(state["params"], "block_000")
    got = registry.extract_unit(restored["params"], "block_000")
    for a, b in zip(jax.tree.leaves(exp), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


# ----------------------------------------------------------- delta tracker
def test_tracker_keeps_no_weight_copies(small_setup):
    model, state, registry = small_setup
    tracker = DeltaTracker(registry, block_bytes=BB)
    tracker.reset(state["params"])
    param_bytes = sum(np.asarray(x).nbytes
                      for x in jax.tree.leaves(state["params"]))
    fp_bytes = sum(
        np.asarray(l.fp).nbytes + np.asarray(l.sumsq).nbytes
        for leaves in tracker._refs.values() for l in leaves)
    assert fp_bytes < param_bytes / 100  # vectors, not reference weights
    scores = tracker.scores(state["params"])
    assert all(v == 0.0 for v in scores.values())


def test_tracker_ranks_magnitude(small_setup):
    model, state, registry = small_setup
    tracker = DeltaTracker(registry, block_bytes=BB)
    tracker.reset(state["params"])
    # big scale on block_002, small (but bf16-representable) nudge on
    # block_001
    params = registry.insert_unit(
        state["params"], "block_002",
        jax.tree.map(lambda x: np.asarray(x) * 1.5,
                     registry.extract_unit(state["params"], "block_002")))
    params = registry.insert_unit(
        params, "block_001",
        jax.tree.map(lambda x: np.asarray(x) * 1.01,
                     registry.extract_unit(params, "block_001")))
    scores = tracker.scores(params)
    assert max(scores, key=scores.get) == "block_002"
    assert scores["block_001"] > scores["block_000"] == 0.0
    assert scores["block_002"] == pytest.approx(0.5, rel=0.05)


# ------------------------------------------------------------ async writer
def test_pending_result_wait():
    w = AsyncWriter(num_threads=1)
    release = threading.Event()

    def slow():
        release.wait(5)
        return 42

    p = w.submit(slow)
    assert not p.done()
    release.set()
    assert p.wait(5)
    assert p.result() == 42
    w.wait()  # the documented alias of drain()
    w.close()


def test_submit_after_close_raises_and_never_hangs():
    w = AsyncWriter(num_threads=2)
    w.close()
    with pytest.raises(AsyncWriteError):
        w.submit(lambda: None)


def test_concurrent_close_and_submit_no_lost_work():
    """Race regression: a submit that wins the open-check must have its
    item processed (never stranded behind the shutdown sentinels)."""
    for _ in range(8):
        w = AsyncWriter(num_threads=2)
        results = []
        stop = threading.Event()

        def submitter():
            i = 0
            while not stop.is_set():
                try:
                    results.append(w.submit(lambda v=i: v))
                except AsyncWriteError:
                    return
                i += 1

        t = threading.Thread(target=submitter)
        t.start()
        time.sleep(0.002)
        stop.set()
        w.close()
        t.join(5)
        assert not t.is_alive()
        for p in results:  # every accepted submit resolved
            assert p.wait(5)

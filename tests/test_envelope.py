"""One-join object framing: every stored object is the blob
``msgpack.packb`` gives, built from pieces that hold the payload by
reference.

The framing (``workers.bin_header``, ``frame_object``,
``encode_chunk_pieces``) must equal ``packb`` byte for byte across the
``bin`` size classes, codecs, dtypes and envelope kinds; and a two-event
parity save must store the manifests and object bytes that the
copy-everything way (``tobytes`` payloads, ``packb`` of the records, then
of the envelope) stores."""
import zlib

import jax
import ml_dtypes
import msgpack
import numpy as np
import pytest

from repro.checkpoint import chunk_store, workers
from repro.checkpoint import fingerprint as fputil
from repro.checkpoint.saver import CheckpointManager
from repro.configs import get_config
from repro.core import LayerRegistry, make_policy
from repro.launch import steps as steps_lib
from repro.models import build_model


def _packb(obj):
    return msgpack.packb(obj, use_bin_type=True)


def _copying_chunk_blob(items, meta, codec):
    """The chunk payload built by copying: each leaf's ``bytes``, each
    record's data packed by ``packb``."""
    tensors = []
    for name, shape, dtype, raw in items:
        data, used, extra = workers.encode_record(bytes(raw), shape, dtype,
                                                  codec)
        data = bytes(data)
        tensors.append({"name": name, "shape": list(shape), "dtype": dtype,
                        "codec": used, "crc": zlib.crc32(data) & 0xFFFFFFFF,
                        "extra": extra, "data": data})
    return _packb({"version": workers.CHUNK_FORMAT_VERSION, "meta": meta,
                   "tensors": tensors})


def _leaves():
    rng = np.random.default_rng(7)
    return {
        "bf16": rng.standard_normal((3, 300)).astype(ml_dtypes.bfloat16),
        "f32": rng.standard_normal((70, 300)).astype(np.float32),
        "i32": rng.integers(-9, 9, (1000,)).astype(np.int32),
        "bool": rng.integers(0, 2, (17, 5)).astype(bool),
        "scalar": np.asarray(2.5, np.float32),
        "empty": np.zeros((0, 4), np.float32),
    }


def _items(leaves):
    return [(f"u/{k}", a.shape, str(a.dtype), fputil.byte_view(a))
            for k, a in leaves.items()]


@pytest.mark.parametrize("n", [0, 1, 255, 256, 65535, 65536, 200_000])
def test_bin_header_is_the_packb_size_class(n):
    data = bytes(range(256)) * (n // 256) + bytes(n % 256)
    assert workers.bin_header(n) + data == _packb(data)


@pytest.mark.parametrize("n", [0, 255, 256, 65535, 65536])
@pytest.mark.parametrize("parts", [1, 3])
def test_framed_payload_at_each_bin_boundary(n, parts):
    """A payload of ``n`` bytes given as one buffer or as pieces."""
    blob = (bytes(range(251)) * (n // 251 + 1))[:n]
    cuts = [0] + sorted({n * i // parts for i in range(1, parts)}) + [n]
    pieces = [memoryview(blob)[a:b] for a, b in zip(cuts, cuts[1:])]
    env = {"v": 1, "format": "full", "codec": "none", "base": None,
           "payload": pieces if parts > 1 else pieces[0]}
    want = _packb(dict(env, payload=blob))
    assert b"".join(workers.frame_object(env)) == want


@pytest.mark.parametrize("codec", ["none", "zstd", "int8"])
@pytest.mark.parametrize("kind", [None, "bf16", "f32", "i32", "bool",
                                  "scalar", "empty"])
def test_chunk_pieces_join_to_the_packb_blob(codec, kind):
    leaves = _leaves()
    if kind is not None:
        leaves = {kind: leaves[kind]}
    items = _items(leaves)
    pieces = workers.encode_chunk_pieces(items, {"step": 3}, codec)
    want = _copying_chunk_blob(items, {"step": 3}, codec)
    assert b"".join(pieces) == want
    assert workers.encode_chunk_items(items, {"step": 3}, codec) == want
    # codec none holds each leaf's own buffer: nothing was copied
    if codec == "none":
        views = [p for p in pieces if isinstance(p, memoryview)]
        assert len(views) == len(items)


def _envelopes(codec):
    items = _items(_leaves())
    full = workers.encode_chunk_pieces(items, {}, codec)
    delta = workers.block_delta_encode(
        [{"name": "u/f32", "shape": [70, 300], "dtype": "float32",
          "nbytes": 84000, "block": 4096, "idx": [0, 5],
          "data": bytes(8192)}], compress="zstd")
    table = bytes(range(200))
    return {
        "fp_full": {"v": 1, "format": "full", "codec": codec, "base": None,
                    "payload": full, "fp": table},
        "fp_block_delta": {"v": 1, "format": "block_delta", "base": "ab" * 20,
                           "payload": delta, "fp": table},
        "canonical_full": {"v": 1, "format": "full", "codec": codec,
                           "base": None, "payload": full},
        "canonical_delta": {"v": 1, "format": "delta", "base": "cd" * 20,
                            "payload": workers.delta_encode(
                                bytes(70000), bytes(69000))},
    }


@pytest.mark.parametrize("codec", ["none", "zstd", "int8"])
@pytest.mark.parametrize("kind", ["fp_full", "fp_block_delta",
                                  "canonical_full", "canonical_delta"])
def test_framed_envelope_is_the_packb_blob(codec, kind):
    env = _envelopes(codec)[kind]
    payload = env["payload"]
    joined = b"".join(payload) if isinstance(payload, list) else payload
    want = _packb(dict(env, payload=joined))
    assert b"".join(workers.frame_object(env)) == want
    assert msgpack.unpackb(want, raw=False)["payload"] == joined


# ------------------------------------------------ a save, both ways
@pytest.fixture(scope="module")
def small():
    cfg = get_config("llama3.2-3b", reduced=True)
    model = build_model(cfg)
    registry = LayerRegistry(model)
    s1 = steps_lib.init_state(model, jax.random.key(0))

    def dense(x):
        return np.asarray(x) + np.asarray(1, np.asarray(x).dtype)

    def sparse(x):
        x = np.array(x)
        x.reshape(-1)[:4] += np.asarray(1, x.dtype)
        return x

    params = jax.tree.map(dense, s1["params"])
    for unit in ("block_000", "block_001"):     # one of them is a delta
        params = registry.insert_unit(params, unit, jax.tree.map(
            sparse, registry.extract_unit(s1["params"], unit)))
    s2 = dict(s1, params=params, opt=jax.tree.map(dense, s1["opt"]))
    return model, registry, s1, s2


def _copying_write_object(self, digest, env):
    """``_write_object`` as ``packb`` of the envelope, the payload joined
    first."""
    payload = env["payload"]
    if isinstance(payload, list):
        payload = b"".join(payload)
    blob = _packb(dict(env, payload=payload))
    self.backend.write(digest, blob)
    with self._lock:
        self._info[digest] = {"stored": env["format"],
                              "base": env.get("base"),
                              "codec": env.get("codec"),
                              "nbytes": len(blob)}
    return len(blob)


def _save_two(root, small, *, fingerprint, io_backend, codec):
    model, registry, s1, s2 = small
    mgr = CheckpointManager(root, registry,
                            make_policy("parity", model.layer_units()),
                            fp_block_bytes=4096, fingerprint=fingerprint,
                            io_backend=io_backend, codec=codec,
                            store_backend="memory")
    try:
        mgr.save(s1, step=10)
        mgr.save(s2, step=20)
        manifests = [mgr.manifests.load(s).to_json() for s in (10, 20)]
        objects = {d: mgr.store.read_object_bytes(d)
                   for d in mgr.store.iter_digests()}
        stored = {e.stored for kinds in mgr.manifests.load(20).entries
                  .values() for e in kinds.values() if e.step == 20}
    finally:
        mgr.close()
    return manifests, objects, stored


@pytest.mark.parametrize("fingerprint,io_backend,codec", [
    (True, "thread", "zstd"),
    (True, "thread", "none"),
    pytest.param(True, "process", "zstd", marks=pytest.mark.process_io),
    (False, "thread", "zstd"),
    pytest.param(False, "process", "zstd", marks=pytest.mark.process_io),
])
def test_parity_save_stores_the_bytes_of_the_copying_way(
        tmp_path, small, monkeypatch, fingerprint, io_backend, codec):
    got = _save_two(tmp_path / "views", small, fingerprint=fingerprint,
                    io_backend=io_backend, codec=codec)
    # the copying way: ``tobytes`` payloads, ``packb`` of the records,
    # then of the envelope (thread backend)
    monkeypatch.setattr(fputil, "byte_view",
                        lambda a: memoryview(np.ascontiguousarray(a)
                                             .tobytes()))
    monkeypatch.setitem(workers.WORKER_FNS, "encode_chunk_pieces",
                        lambda items, meta, codec: [
                            _copying_chunk_blob(items, meta, codec)])
    monkeypatch.setattr(chunk_store.ChunkStore, "_write_object",
                        _copying_write_object)
    want = _save_two(tmp_path / "copies", small, fingerprint=fingerprint,
                     io_backend="thread", codec=codec)
    assert got[0] == want[0], "manifests differ"
    assert got[1].keys() == want[1].keys()
    for d, blob in want[1].items():
        assert got[1][d] == blob, f"object {d} differs"
    # the second event stored full objects and deltas both
    assert got[2] == {"full", "delta"}

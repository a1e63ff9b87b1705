"""Operation and byte counts on shapes worked out by hand."""
from __future__ import annotations

import jax
import pytest

from bench.common import counts
from bench.common.peaks import peaks_for


def test_six_n_t():
    leaves = [(("embed", "w"), (100, 8)), (("blocks", "w"), (2, 8, 8)),
              (("final_norm", "scale"), (8,))]
    assert counts.active_params(leaves, tie_embeddings=True) == 800 + 128 + 8
    assert counts.active_params(leaves, tie_embeddings=False) == 128 + 8
    assert counts.train_flops(936, 1000) == 6 * 936 * 1000


def test_reference_adamw_decays_by_each_layers_ndim():
    """Two stacked roots: a leaf under either has one dimension fewer per
    layer than its array, so a stacked vector is exempt from weight decay
    and a stacked matrix is not.  With a loss of zero only the decay
    moves a leaf."""
    import jax.numpy as jnp
    import numpy as np

    from bench.ref import adamw

    master = {"blocks": {"w": jnp.ones((3, 4, 4)), "b": jnp.ones((3, 4))},
              "hybrid": {"mamba": {"w": jnp.ones((2, 4, 4)),
                                   "b": jnp.ones((2, 4))}},
              "head": {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}}
    opt = {"learning_rate": 0.1, "warmup_steps": 0, "total_steps": 10,
           "weight_decay": 0.5, "adam_b1": 0.9, "adam_b2": 0.95,
           "adam_eps": 1e-8, "grad_clip_norm": 1.0}
    seen = []

    def no_decay(path, ndim):
        seen.append(("/".join(path), ndim))
        return ndim <= 1

    _, _, out = adamw.train(
        lambda p, t: 0.0 * sum(jnp.sum(x) for x in jax.tree.leaves(p)),
        master, [np.zeros((2, 3), np.int32)], opt,
        stacked_roots=[("blocks",), ("hybrid", "mamba")], no_decay=no_decay)
    assert sorted(seen) == [("blocks/b", 1), ("blocks/w", 2),
                            ("head/b", 1), ("head/w", 2),
                            ("hybrid/mamba/b", 1), ("hybrid/mamba/w", 2)]
    moved = {"/".join(getattr(k, "key", "") for k in kp):
             bool(jnp.any(leaf != 1.0))
             for kp, leaf in jax.tree_util.tree_flatten_with_path(out)[0]}
    assert moved == {"blocks/b": False, "blocks/w": True, "head/b": False,
                     "head/w": True, "hybrid/mamba/b": False,
                     "hybrid/mamba/w": True}


@pytest.mark.parametrize("shape,dtype,want", [
    # 1 KiB of f32: one 64 KiB block; the leaf, one checksum pair (8 B)
    # and one sum of squares (4 B)
    ((256,), "float32", 1024 + 12),
    # 1024 x 1024 bf16 = 2 MiB = 32 blocks
    ((1024, 1024), "bfloat16", 2 * 2**20 + 32 * 12),
    # 10 blocks of f32 and one element -> 11 blocks
    ((10 * 16384 + 1,), "float32", (10 * 16384 + 1) * 4 + 11 * 12),
    # int32 is not decoded: no sum of squares written
    ((16384,), "int32", 65536 + 8),
])
def test_fingerprint_bytes(shape, dtype, want):
    assert counts.fingerprint_bytes(shape, dtype) == want


def test_peaks_are_keyed_by_device_kind():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("cpu")

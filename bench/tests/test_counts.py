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


@pytest.fixture(scope="module")
def deepseek_leaves():
    from bench.common import program
    from bench.common.checksum import leaves_with_paths
    from bench.tests.conftest import TINY

    specs = program.state_specs(program.build(TINY["deepseek-v2-lite-16b"]))
    return [(p, s.shape) for p, s in leaves_with_paths(specs["params"])]


#: the tiny DeepSeek's leaves by hand (untied: the embedding is left out)
_ATTN = 32 + 64 * 32 + 64 * 8 + 2 * 32 * 4 * 16 + 4 * 16 * 64 + 64 * 4 * 24
_NORMS = 2 * 64
_ROUTER, _SHARED = 64 * 4, 3 * 64 * 32
_EXPERTS = 3 * 4 * 64 * 32               # we_gate, we_up, we_down: 4 held
_DENSE_FIRST = _ATTN + _NORMS + 3 * 64 * 128
_OUT = 64 + 64 * 256                     # final norm, lm_head
_MOE_DENSE = _ATTN + _NORMS + _ROUTER + _SHARED


@pytest.mark.parametrize("routed,want", [
    # no ``routed`` key: every held expert counts whole, as before
    (False, _DENSE_FIRST + 3 * (_MOE_DENSE + _EXPERTS) + _OUT),
    # 2 of 4 experts per token: the experts at half, the shared expert
    # and the router whole
    (True, _DENSE_FIRST + 3 * (_MOE_DENSE + _EXPERTS * 2 // 4) + _OUT),
], ids=["no-routed", "routed"])
def test_routed_experts_count_at_their_published_share(routed, want,
                                                       deepseek_leaves):
    from bench.tests.conftest import TINY

    cfg = TINY["deepseek-v2-lite-16b"]
    n = counts.active_params(
        deepseek_leaves, tie_embeddings=False,
        routed=cfg["routed"] if routed else None, config=cfg["name"])
    assert n == want


@pytest.mark.parametrize("routed,match", [
    ({"paths": ["blocks/moe/we_gate", "blocks/moe/w_up"], "experts": 4,
      "per_token": 2}, r"\['blocks/moe/w_up'\] match no parameter"),
    ({"paths": ["blocks/moe/we"], "experts": 4, "per_token": 2},
     "match no parameter"),
    ({"paths": ["blocks/moe/we_gate"], "experts": 4, "per_token": 6},
     "per_token <= experts"),
    ({"paths": [], "experts": 4, "per_token": 2}, "needs paths"),
], ids=["typo", "partial-name", "per-token-over-experts", "no-paths"])
def test_a_routed_key_that_counts_wrong_is_refused(routed, match,
                                                    deepseek_leaves):
    with pytest.raises(ValueError, match="^tiny-deepseek: .*" + match):
        counts.active_params(deepseek_leaves, tie_embeddings=False,
                             routed=routed, config="tiny-deepseek")


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

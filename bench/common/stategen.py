"""Train states drawn from the seed, on the device, in one jitted call.

The harness makes every weight it hands the program, so the reference
never takes a number the program made.  Leaves follow the usual Mamba2 /
transformer initialisation by name: norms and skip gains one, ``A_log``
the log of 1..16 over the heads, biases zero, the depthwise convolution
0.2, embeddings and output heads 0.02, every other matrix
1/sqrt(fan-in), all truncated normals at two sigma.  ``master`` holds the
float32 draw, ``params`` its bfloat16 cast, as after an optimizer step.
With ``moments`` the Adam moments are filled as after some training
(m ~ 1e-4 N(0, 1), v = m^2 + 1e-10); without, they are zero (step 0).
"""
from __future__ import annotations

import zlib
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.common.checksum import is_stacked, leaves_with_paths

ONES = ("ln", "ln1", "ln2", "scale", "out_norm", "D_skip")
ZEROS = ("dt_bias", "conv_b")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed (wider than 32 bits too)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _leaf_key(key, path: Tuple[str, ...]):
    return jax.random.fold_in(key, zlib.crc32("/".join(path).encode())
                              & 0x7FFFFFFF)


def init_leaf(key, path: Tuple[str, ...], shape, stacked: bool) -> jax.Array:
    name = path[-1]
    layer_shape = tuple(shape[1:]) if stacked else tuple(shape)
    if name in ONES:
        return jnp.ones(shape, jnp.float32)
    if name in ZEROS:
        return jnp.zeros(shape, jnp.float32)
    if name == "A_log":
        return jnp.broadcast_to(
            jnp.log(jnp.linspace(1.0, 16.0, layer_shape[-1],
                                 dtype=jnp.float32)), shape)
    if name == "conv_w":
        scale = 0.2
    elif path[0] in ("embed", "lm_head"):
        scale = 0.02
    else:
        scale = 1.0 / np.sqrt(layer_shape[0])
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * scale


def make_state_fn(param_specs, stacked_roots: Sequence[Tuple[str, ...]], *,
                  moments: bool):
    """A jitted ``(key, step) -> {"params", "opt", "step"}``."""
    flat = list(leaves_with_paths(param_specs))

    def unflatten(values):
        out: Dict = {}
        for (path, _), v in zip(flat, values):
            node = out
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = v
        return out

    @jax.jit
    def make(key, step):
        master, m, v = [], [], []
        for path, spec in flat:
            stacked = is_stacked(("params",) + path, stacked_roots)
            x = init_leaf(_leaf_key(key, path), path, spec.shape, stacked)
            master.append(x)
            if moments:
                g = jax.random.normal(_leaf_key(key, ("m",) + path),
                                      spec.shape, jnp.float32) * 1e-4
                m.append(g)
                v.append(g * g + 1e-10)
            else:
                m.append(jnp.zeros(spec.shape, jnp.float32))
                v.append(jnp.zeros(spec.shape, jnp.float32))
        return {"params": unflatten([x.astype(jnp.bfloat16)
                                     for x in master]),
                "opt": {"master": unflatten(master), "m": unflatten(m),
                        "v": unflatten(v)},
                "step": jnp.asarray(step, jnp.int32)}

    return make


def make_master_fn(param_specs, stacked_roots):
    """A jitted ``key -> master params (float32)``, the same draw as
    ``make_state_fn``'s ``opt.master``."""
    make = make_state_fn(param_specs, stacked_roots, moments=False)
    return jax.jit(lambda key: make(key, 0)["opt"]["master"])

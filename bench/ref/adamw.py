"""Plain AdamW of every reference: global-norm clipping, bias-corrected
moments, decoupled weight decay, a linear warm-up to the peak learning
rate and a cosine to a tenth of it.  The gradient of a step is the
summed loss's over blocks of rows, divided by the step's target tokens.

Weight decay follows the reference's own ``no_decay(path, ndim)``, where
``ndim`` counts a layer's dimensions: a leaf under a stacked root (a
group of layers held with a leading layer axis) has one fewer than its
array.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

#: rows per gradient call, so that a full-size reference fits the chip
ROWS_PER_BLOCK = 2


def lr_at(step: int, opt: Dict) -> float:
    """Linear warm-up to the peak, then a cosine to a tenth of it."""
    peak, warm, total = (opt["learning_rate"], opt["warmup_steps"],
                         opt["total_steps"])
    if step < warm:
        return peak * step / max(warm, 1)
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * t)))


def train(loss_sum: Callable, master, batches, opt: Dict, *,
          stacked_roots: Sequence[Tuple[str, ...]],
          no_decay: Callable[[Tuple[str, ...], int], bool]):
    """AdamW from ``master`` over ``batches`` (one (B, S) token array per
    step); ``loss_sum(params, tokens)`` is the summed loss of a block of
    rows.  Returns per-step losses, the first step's clipped gradient,
    and the final master tree."""
    grad_block = jax.jit(jax.value_and_grad(loss_sum))
    b1, b2, eps, wd = (opt["adam_b1"], opt["adam_b2"], opt["adam_eps"],
                       opt["weight_decay"])
    paths, treedef = jax.tree_util.tree_flatten_with_path(master)
    decay = []
    for kp, leaf in paths:
        names = tuple(getattr(k, "key", str(k)) for k in kp)
        stacked = any(names[:len(r)] == r for r in stacked_roots)
        decay.append(not no_decay(names, leaf.ndim - (1 if stacked else 0)))
    m = jax.tree.map(jnp.zeros_like, master)
    v = jax.tree.map(jnp.zeros_like, master)
    losses, first_grad = [], None
    for step, tokens in enumerate(batches):
        rows, seq = tokens.shape
        total, grads = 0.0, None
        for r in range(0, rows, ROWS_PER_BLOCK):
            val, g = grad_block(master, jnp.asarray(tokens[r:r + ROWS_PER_BLOCK]))
            total += float(val)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        count = rows * (seq - 1)
        losses.append(total / count)
        grads = jax.tree.map(lambda g: g / count, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt["grad_clip_norm"] / jnp.maximum(gnorm, 1e-12))
        grads = jax.tree.map(lambda g: g * scale, grads)
        if first_grad is None:
            first_grad = grads
        lr = lr_at(step, opt)
        t = step + 1.0
        c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
        flat_g = treedef.flatten_up_to(grads)
        flat_p = treedef.flatten_up_to(master)
        flat_m = treedef.flatten_up_to(m)
        flat_v = treedef.flatten_up_to(v)
        new_p, new_m, new_v = [], [], []
        for g, p, mi, vi, dec in zip(flat_g, flat_p, flat_m, flat_v, decay):
            mi = b1 * mi + (1 - b1) * g
            vi = b2 * vi + (1 - b2) * g * g
            upd = (mi / c1) / (jnp.sqrt(vi / c2) + eps)
            p = p - lr * (upd + (wd * p if dec else 0.0))
            new_p.append(p)
            new_m.append(mi)
            new_v.append(vi)
        master = treedef.unflatten(new_p)
        m = treedef.unflatten(new_m)
        v = treedef.unflatten(new_v)
    return losses, first_grad, master

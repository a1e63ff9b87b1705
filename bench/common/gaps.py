"""The numbers ``correct`` compares, each worked out the same way in
every driver."""
from __future__ import annotations

import statistics
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.common.checksum import leaves_with_paths


@jax.jit
def leaf_norms(tree):
    """float32 norm of every leaf, in sorted-path order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for _, x in leaves_with_paths(tree)])


@jax.jit
def diff_norms(a, b):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for (_, x), (_, y) in zip(leaves_with_paths(a),
                                  leaves_with_paths(b))])


def worst_leaf(prog: Sequence[float], ref: Sequence[float],
               keep: Optional[Sequence[bool]] = None) -> float:
    """max over leaves of |prog - ref| / max(ref, median ref), the gap
    between the two norms, not the norm of their difference."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(len(ref), bool) if keep is None else np.asarray(keep)
    med = statistics.median(ref[keep].tolist())
    return float(np.max(np.abs(prog - ref)[keep]
                        / np.maximum(ref[keep], med)))


def moved(ref_grad: Sequence[float], frac: float = 1e-3) -> np.ndarray:
    """Leaves whose reference gradient is not nought to rounding: at
    least ``frac`` of the median leaf's."""
    g = np.asarray(ref_grad, np.float64)
    return g >= frac * statistics.median(g.tolist())


def max_rel(prog: Sequence[float], ref: Sequence[float]) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def logit_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Widest gap by which a chosen token's reference logit lies below
    the reference's best.  ref_logits (..., V), tokens (...)."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, tokens[..., None], axis=-1)[..., 0]
    return float(np.max(best - got))


def to_host(x) -> np.ndarray:
    return np.asarray(jax.device_get(x))

"""Plain reference of the Mamba2 language model (Dao and Gu, "Transformers
are SSMs", arXiv:2405.21060) and its loss, trained by ``bench.ref.adamw``.

Written in straightforward ``jax.numpy`` from the published equations, with
no kernel, cache, chunking or batching trick, and nothing imported from
the program.  The state-space mixer is evaluated in its quadratic
("attention") form:

    y_t = sum_{u <= t} (C_t . B_u) exp(sum_{u < r <= t} dt_r A) dt_u x_u + D x_t

Layer (pre-norm residual):  h += W_out . RMSNorm(y * silu(z)), where
z, x, B, C, dt come from linear maps of RMSNorm(h), x|B|C pass a causal
depthwise convolution and silu, dt = softplus(. + dt_bias), A = -exp(A_log).
The output head is the transposed embedding when tied.  Departures from
the published model, shared with the program: ngroups B/C groups are
repeated over heads; the gated RMSNorm normalises the whole inner width
(not per group).

``mm`` sets the precision of every matrix product: float32 at
``Precision.HIGHEST`` (the reference), or the operands rounded to a
narrower type first (the control, ``float8_e4m3fn``).  Parameter layout:
the stacked tree the program's checkpoints hold (``blocks/<leaf>`` with a
leading layer axis).  The module keeps the contract of ``bench.ref``.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from bench.ref import adamw

F32 = jnp.float32


class Sizes(NamedTuple):
    d_model: int
    state_dim: int
    head_dim: int
    expand: int
    conv_kernel: int
    ngroups: int
    vocab_size: int
    tie_embeddings: bool
    norm_eps: float

    @staticmethod
    def of(cfg: Dict) -> "Sizes":
        s = cfg["ssm"]
        return Sizes(cfg["d_model"], s["state_dim"], s["head_dim"],
                     s["expand"], s["conv_kernel"], s.get("ngroups", 1),
                     cfg["vocab_size"], cfg.get("tie_embeddings", False),
                     cfg.get("norm_eps", 1e-5))


def make_mm(dtype: Optional[str] = None):
    """Matrix products in float32 (``dtype`` None) or with both operands
    rounded to ``dtype`` and accumulated in float32."""
    if dtype is None:
        def mm(spec, a, b):
            return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                              precision=jax.lax.Precision.HIGHEST)
    else:
        low = jnp.dtype(dtype)

        def mm(spec, a, b):
            ra = a.astype(low).astype(jnp.bfloat16)
            rb = b.astype(low).astype(jnp.bfloat16)
            return jnp.einsum(spec, ra, rb, preferred_element_type=F32)
    return mm


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def ssd(mm, x, dt, a, b, c):
    """x (B,S,H,P), dt (B,S,H), a (H,) negative, b, c (B,S,H,N)."""
    s = x.shape[1]
    cum = jnp.cumsum(dt * a, axis=1)                       # (B,S,H)
    seg = cum[:, :, None, :] - cum[:, None, :, :]          # (B,t,u,H)
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = mm("bthn,buhn->btuh", c, b)
    w = cb * decay * dt[:, None, :, :]
    return mm("btuh,buhp->bthp", w, x)


def block(mm, sz: Sizes, p, h):
    d_in = sz.expand * sz.d_model
    n_heads = d_in // sz.head_dim
    gn = sz.ngroups * sz.state_dim
    bsz, s, _ = h.shape
    x = rms_norm(h, p["ln"], sz.norm_eps)
    z = mm("bsd,de->bse", x, p["mixer"]["w_z"])
    xbc = jnp.concatenate([mm("bsd,de->bse", x, p["mixer"]["w_x"]),
                           mm("bsd,de->bse", x, p["mixer"]["w_B"]),
                           mm("bsd,de->bse", x, p["mixer"]["w_C"])], -1)
    dt = jax.nn.softplus(mm("bsd,dh->bsh", x, p["mixer"]["w_dt"])
                         + p["mixer"]["dt_bias"].astype(F32))
    k = sz.conv_kernel
    w_conv = p["mixer"]["conv_w"].astype(F32)              # (K, C)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + s] * w_conv[i] for i in range(k))
    xbc = jax.nn.silu(conv + p["mixer"]["conv_b"].astype(F32))
    xs = xbc[..., :d_in].reshape(bsz, s, n_heads, sz.head_dim)

    def heads(t):
        t = t.reshape(bsz, s, sz.ngroups, sz.state_dim)
        return jnp.repeat(t, n_heads // sz.ngroups, axis=2)

    bm = heads(xbc[..., d_in:d_in + gn])
    cm = heads(xbc[..., d_in + gn:])
    a = -jnp.exp(p["mixer"]["A_log"].astype(F32))
    y = ssd(mm, xs, dt, a, bm, cm)
    y = y + p["mixer"]["D_skip"].astype(F32)[:, None] * xs
    y = y.reshape(bsz, s, d_in) * jax.nn.silu(z)
    y = rms_norm(y, p["mixer"]["out_norm"], sz.norm_eps)
    return h + mm("bse,ed->bsd", y, p["mixer"]["w_out"])


def logits(mm, sz: Sizes, params, tokens):
    h = jnp.take(params["embed"]["w"].astype(F32), tokens, axis=0)

    def body(hh, p):
        return block(mm, sz, p, hh), None

    h, _ = jax.lax.scan(jax.checkpoint(body), h, params["blocks"])
    h = rms_norm(h, params["final_norm"]["scale"], sz.norm_eps)
    if sz.tie_embeddings:
        return mm("bsd,vd->bsv", h, params["embed"]["w"])
    return mm("bsd,dv->bsv", h, params["lm_head"]["w"])


def nll_sum(mm, sz: Sizes, params, tokens):
    """Summed next-token negative log-likelihood of a block of rows."""
    lg = logits(mm, sz, params, tokens[:, :-1])
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


# --------------------------------------------------------------- training
def no_decay(path, leaf_ndim: int) -> bool:
    """AdamW convention: norms, biases, gains and 1-d leaves are exempt."""
    name = path[-1]
    return (leaf_ndim <= 1 or any(t in name for t in (
        "ln", "norm", "bias", "scale", "A_log", "D_skip", "dt_bias")))


def train(mm_dtype: Optional[str], sz: Sizes, master, batches, opt: Dict,
          *, stacked_roots):
    """Plain AdamW (``bench.ref.adamw``) of this model's summed loss."""
    mm = make_mm(mm_dtype)
    return adamw.train(lambda p, t: nll_sum(mm, sz, p, t), master, batches,
                       opt, stacked_roots=stacked_roots, no_decay=no_decay)

"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Prototype (tested at small scale in tests/test_pipeline.py): stages are laid
out on a ``stage`` mesh axis; microbatches stream through with activations
hopping stage->stage+1 by collective_permute each tick.  With S stages and M
microbatches the schedule runs M + S - 1 ticks (bubble fraction
(S-1)/(M+S-1) — the standard GPipe trade-off).

The production configs in this repo use FSDP+TP (every assigned arch fits a
pod that way); PP is provided for the scales where that stops being true —
wire it by stacking block groups as stages.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def pipeline_apply(
    stage_fn: Callable[[PyTree, jax.Array], jax.Array],
    stage_params: PyTree,      # leaves stacked (S, ...) over stages
    x: jax.Array,              # (M, mb, ...) microbatches
    mesh: Mesh,
    axis: str = "stage",
) -> jax.Array:
    """Runs x through S chained stages; returns (M, mb, ...) outputs."""
    s = mesh.shape[axis]
    m = x.shape[0]

    def local(params, xs):
        # params: (1, ...) this stage's slice; xs: (M, mb, ...) full stream
        # (only stage 0 consumes it; others ignore).
        params = jax.tree.map(lambda t: t[0], params)
        idx = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        # Mark carries as device-varying along the stage axis up front so the
        # fori_loop carry types stay stable (shard_map vma typing).
        state = jax.lax.pcast(jnp.zeros(mb_shape, xs.dtype), (axis,),
                              to="varying")
        outs = jax.lax.pcast(jnp.zeros((m,) + mb_shape, xs.dtype), (axis,),
                             to="varying")

        def tick(t, carry):
            state, outs = carry
            # stage 0 ingests microbatch t (when in range)
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, m - 1), axis=0, keepdims=False)
            inp = jnp.where((idx == 0) & (t < m), feed, state)
            out = stage_fn(params, inp)
            # last stage emits microbatch t-(S-1)
            emit_t = t - (s - 1)
            emit = (idx == s - 1) & (emit_t >= 0)
            upd = jax.lax.dynamic_update_index_in_dim(
                outs, out, jnp.clip(emit_t, 0, m - 1), axis=0)
            outs = jnp.where(emit, upd, outs)
            # rotate activations one stage forward
            nxt = jax.lax.ppermute(
                out, axis, perm=[(i, (i + 1) % s) for i in range(s)])
            return (nxt, outs)

        state, outs = jax.lax.fori_loop(0, m + s - 1, tick, (state, outs))
        # Outputs accumulated on the last stage; rotate them to stage 0 and
        # psum-broadcast so every shard returns the same replicated value.
        outs = jax.lax.ppermute(
            outs, axis, perm=[(i, (i + 1) % s) for i in range(s)])
        outs = jax.lax.psum(
            jnp.where(idx == 0, outs, jnp.zeros_like(outs)), axis)
        return outs

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )
    return fn(stage_params, x)

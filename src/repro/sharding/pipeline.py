"""GPipe-style pipeline parallelism over a 'pipe' mesh axis.

The production 512-chip mesh covers its memory budget with FSDP x TP x SP
(EXPERIMENTS.md §Dry-run), so PP is not part of the 40-cell matrix; this
module provides the stage wrapper for deeper-than-memory models or meshes
with a dedicated 'pipe' axis (e.g. (pipe=4, data=8, model=16) at 512 chips).

Schedule: synchronous GPipe. M microbatches flow through P stages in
M + P - 1 ticks; each tick every device runs its stage on its current
activation and ppermutes the result to the next stage. Bubble fraction
(P-1)/(M+P-1) — the caller picks M >> P.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def gpipe(stage_fn: Callable, stage_params, x_micro: jnp.ndarray, *, mesh: Mesh,
          axis: str = "pipe"):
    """Run ``stage_fn(params_i, x)`` as a P-stage pipeline.

    stage_params: pytree whose leaves have a leading stage dim (P, ...).
    x_micro: (M, micro_batch, ...) microbatched input.
    Returns (M, micro_batch, ...) outputs of the final stage.
    """
    n_stages = mesh.shape[axis]
    m = x_micro.shape[0]

    pspec = jax.tree.map(lambda _: P(axis), stage_params)

    def body(params_local, xs):
        params_i = jax.tree.map(lambda a: a[0], params_local)  # this stage's params
        idx = jax.lax.axis_index(axis)
        xs = xs[0]                                             # (M, mb, ...) replicated payload
        zero = jnp.zeros_like(xs[0])

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (while t < M); others take the
            # activation handed over from the previous stage last tick
            feed = jnp.where(t < m, xs[jnp.minimum(t, m - 1)], zero)
            inp = jnp.where(idx == 0, feed, buf)
            act = stage_fn(params_i, inp)
            # hand to the next stage
            nxt = jax.lax.ppermute(act, axis, [(i, i + 1) for i in range(n_stages - 1)])
            # last stage emits microbatch t-(P-1) at tick t
            emit_t = t - (n_stages - 1)
            is_emit = (emit_t >= 0) & (idx == n_stages - 1)
            outs = jax.lax.cond(
                is_emit,
                lambda o: o.at[jnp.maximum(emit_t, 0)].set(act),
                lambda o: o,
                outs,
            )
            return (nxt, outs), None

        outs0 = jnp.zeros((m,) + xs.shape[1:], xs.dtype) + zero[None] * 0
        (_, outs), _ = jax.lax.scan(tick, (zero, outs0), jnp.arange(m + n_stages - 1))
        # broadcast the last stage's outputs to every pipe rank
        outs = jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)[None]

    in_specs = (pspec, P(axis))  # payload replicated via leading fake stage dim
    xs_tiled = jnp.broadcast_to(x_micro[None], (n_stages,) + x_micro.shape)
    out = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=P(axis))(
        stage_params, xs_tiled)
    return out[0]


def sequential_reference(stage_fn: Callable, stage_params, x_micro: jnp.ndarray):
    """Oracle: apply the P stages in sequence to each microbatch."""
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def one(x):
        for i in range(n_stages):
            params_i = jax.tree.map(lambda a: a[i], stage_params)
            x = stage_fn(params_i, x)
        return x

    return jax.vmap(one)(x_micro)

"""Pipeline parallelism (PP): GPipe-style microbatch pipeline over a mesh
axis, built from ``shard_map`` + ``lax.ppermute``.

Stages own contiguous layer groups (stage s holds params[s]); microbatches
stream through: at tick t, stage s runs microbatch (t - s).  The schedule
costs the classic GPipe bubble (stages-1)/(ticks) — the autosharding
advisor accounts for it when scoring PP against FSDPxTP layouts.  The whole
loop is differentiable (grad flows back through the reversed ppermutes), so
``pipeline_forward`` drops into the standard train step; combine with remat
for 1F1B-class memory behavior.

Layout contract:
  * ``params``: pytree with leading STAGE axis, sharded P("stage", ...)
  * ``x_mb``:   (n_micro, mb, ...) microbatched inputs (replicated over the
    stage axis; only stage 0 consumes them)
  * returns (n_micro, mb, ...) outputs (only stage L-1's results are real;
    they are gathered back to all stages)
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, mesh: Mesh, axis: str,
                     params, x_mb):
    """Run the pipeline.  stage_fn(stage_params, x) -> y applies ONE stage's
    layer group; stage_params has the stage axis already stripped."""
    stages = mesh.shape[axis]
    n_micro = x_mb.shape[0]
    ticks = n_micro + stages - 1

    pspec = jax.tree.map(lambda _: P(axis), params)
    others = tuple(a for a in mesh.axis_names if a != axis)

    @partial(jax.shard_map, mesh=mesh, check_vma=False,
             in_specs=(pspec, P()), out_specs=P())
    def run(p_local, xs):
        p_local = jax.tree.map(lambda a: a[0], p_local)   # strip stage dim
        s = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry
            mb_in = t - 0
            xin0 = jnp.where(mb_in < n_micro,
                             xs[jnp.clip(mb_in, 0, n_micro - 1)], 0.0)
            xin = jnp.where(s == 0, xin0, buf)
            y = stage_fn(p_local, xin)
            # forward the activation to the next stage
            nxt = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(stages - 1)])
            mb_out = t - (stages - 1)
            outs = jnp.where(
                (s == stages - 1) & (mb_out >= 0) & (mb_out < n_micro),
                outs.at[jnp.clip(mb_out, 0, n_micro - 1)].set(y), outs)
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # broadcast the last stage's collected outputs to every stage
        # (only stage L-1 holds real data; psum is a masked broadcast)
        outs = jax.lax.psum(
            jnp.where(s == stages - 1, outs, 0.0), axis)
        return outs

    return run(params, x_mb)


def split_stages(params, n_layers: int, stages: int):
    """Reshape layer-stacked params (L, ...) -> (stages, L/stages, ...)."""
    assert n_layers % stages == 0
    g = n_layers // stages
    return jax.tree.map(
        lambda a: a.reshape((stages, g) + a.shape[1:]), params)

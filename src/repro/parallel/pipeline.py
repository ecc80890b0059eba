"""Pipeline parallelism via shard_map + collective_permute (GPipe schedule).

DFModel's inter-chip pass emits PP stage boundaries (paper §IV); this module
executes them: each device along the 'stage' mesh axis owns one stage's
layer stack and microbatches flow through a collective_permute ring.

The schedule is the classic GPipe fill-steady-drain loop: T = n_micro +
n_stages - 1 ticks; at tick t, stage s processes microbatch t - s. The
bubble fraction (n_stages-1)/T is exactly the term DFModel's iteration model
charges (core/interchip.py).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(mesh: Mesh, stage_fn: Callable, n_stages: int,
                     axis: str = "stage"):
    """Build fn(stage_params, x_micro) -> y_micro running the GPipe schedule.

    stage_params: pytree with leading (n_stages, ...) dims, sharded one
    stage per device along ``axis``.
    x_micro: (n_micro, mb, ...) microbatched input (replicated along axis).
    stage_fn(params_slice, x) -> y must be shape-preserving (d_model in/out),
    as in a transformer trunk.
    """

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(axis), P()),
             out_specs=P(), check_vma=False)
    def run(params, xs):
        params = jax.tree.map(lambda a: a[0], params)  # this stage's slice
        sidx = jax.lax.axis_index(axis)
        n_micro = xs.shape[0]
        total = n_micro + n_stages - 1
        state = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            state, outs = carry
            mb_idx = t - sidx
            # stage 0 ingests microbatch t (if valid); others use the
            # permuted activation from the previous stage
            feed = jnp.where(
                (mb_idx >= 0) & (mb_idx < n_micro),
                jax.lax.dynamic_index_in_dim(
                    xs, jnp.clip(mb_idx, 0, n_micro - 1), 0, keepdims=False),
                jnp.zeros_like(xs[0]))
            x_in = jnp.where(sidx == 0, feed, state)
            y = stage_fn(params, x_in)
            # last stage records its finished microbatch
            outs = jnp.where(
                (sidx == n_stages - 1) & (mb_idx >= 0) & (mb_idx < n_micro),
                jax.lax.dynamic_update_index_in_dim(
                    outs, y, jnp.clip(mb_idx, 0, n_micro - 1), 0),
                outs)
            # pass activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            state = jax.lax.ppermute(y, axis, perm)
            return (state, outs), None

        (_, outs), _ = jax.lax.scan(tick, (state, outs), jnp.arange(total))
        # every device now holds only its own writes; the last stage owns the
        # real outputs — broadcast them
        outs = jax.lax.psum(
            jnp.where(sidx == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    return run

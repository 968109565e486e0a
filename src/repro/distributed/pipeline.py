"""GPipe-style pipeline parallelism via shard_map + ppermute.

Opt-in substrate for depth-dominated models: the layer-stacked params of a
uniform group are split into S stages along the stacked dim; microbatches
stream through stages with `jax.lax.ppermute` boundary transfers inside
`shard_map` over a `pipe` mesh axis.

Schedule: standard GPipe fill-drain over M microbatches — bubble fraction
(S-1)/(M+S-1).  Each device runs `scan` over M+S-1 ticks; at tick t it
processes microbatch t - stage_idx (when valid).

This is deliberately the simple schedule: it is compile-time-fast
(one scan), correct for any stage-uniform block, and sufficient to prove
the distribution config end-to-end on placeholder devices.  1F1B /
circular schedules are noted as future work in DESIGN.md.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def pipeline_forward(
    fn: Callable[[PyTree, jax.Array], jax.Array],
    mesh: Mesh,
    axis: str = "pipe",
):
    """Build a pipelined forward for a stage function.

    fn(stage_params, x) -> x  applies ONE stage (a chunk of layers).
    Returns pipe_fn(stacked_stage_params, microbatches) -> outputs where
      stacked_stage_params : leaves (S, ...)   (S = mesh[axis])
      microbatches         : (M, mb, ...) input microbatches
    """
    S = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(None)),
        out_specs=P(None),
        check_vma=False,
    )
    def pipe_fn(stage_params, microbatches):
        # stage_params leaves arrive as (1, ...) local slices
        local = jax.tree_util.tree_map(lambda t: t[0], stage_params)
        stage = jax.lax.axis_index(axis)
        M = microbatches.shape[0]
        T = M + S - 1
        mb_shape = microbatches.shape[1:]

        def tick(carry, t):
            buf, outs = carry  # buf: the activation entering this stage
            # stage 0 ingests microbatch t (if any)
            mb_idx = jnp.clip(t, 0, M - 1)
            fresh = jax.lax.dynamic_index_in_dim(
                microbatches, mb_idx, 0, keepdims=False
            )
            x_in = jnp.where(stage == 0, fresh, buf)
            active = (t - stage >= 0) & (t - stage < M)
            y = fn(local, x_in)
            y = jnp.where(active, y, buf)
            # last stage commits its output for microbatch t-(S-1)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            commit = (stage == S - 1) & (t - (S - 1) >= 0)
            outs = jax.lax.cond(
                commit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, out_idx, 0
                ),
                lambda o: o,
                outs,
            )
            # shift activations to the next stage
            y_next = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)]
            )
            return (y_next, outs), ()

        buf0 = jnp.zeros(mb_shape, microbatches.dtype)
        outs0 = jnp.zeros((M, *mb_shape), microbatches.dtype)
        (_, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(T)
        )
        # every device holds the last stage's outs copy only on stage S-1;
        # broadcast it: outs is nonzero only there -> psum picks it
        outs = jax.lax.psum(
            jnp.where(stage == S - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    return pipe_fn


def make_pipe_mesh(num_stages: int):
    """Small helper used by tests: 1-D pipe mesh over available devices."""
    devs = jax.devices()[:num_stages]
    import numpy as np

    return Mesh(np.array(devs), ("pipe",))

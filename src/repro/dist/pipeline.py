"""GPipe-style pipeline parallelism over one mesh axis.

Each device holds one stage's weights; microbatches enter at stage 0, flow
stage-to-stage over a ``ppermute`` ring (one hop per step), and exit at the
last stage.  The schedule is the classic fill/steady/drain pipeline:
``M + S - 1`` steps for ``M`` microbatches over ``S`` stages, every device
busy in the steady state.  Invalid (fill/drain) slots execute the block on
don't-care data and are masked out of the output — uniform control flow, the
same predication trick the colskip kernels use for data-dependent work.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["make_pipelined_fn"]


def make_pipelined_fn(mesh, block_fn, axis_name: str):
    """Build ``run(ws, xs)`` computing the sequential stage composition.

    ``ws``: (S, ...) per-stage weights (S = mesh axis size); ``xs``: (M, ...)
    microbatches.  ``run(ws, xs)[m]`` equals
    ``block_fn(ws[S-1], ... block_fn(ws[0], xs[m]))`` for every microbatch.
    """
    n_stages = mesh.shape[axis_name]

    def stage_local(w_local, xs):
        w = w_local[0]                               # this stage's weights
        stage = jax.lax.axis_index(axis_name)
        m = xs.shape[0]
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def step(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t; others consume the ring buffer
            inp = jnp.where(stage == 0, xs[jnp.minimum(t, m - 1)], buf)
            y = block_fn(w, inp)
            out_t = t - (n_stages - 1)               # microbatch exiting now
            idx = jnp.clip(out_t, 0, m - 1)
            write = (stage == n_stages - 1) & (out_t >= 0)
            outs = outs.at[idx].set(jnp.where(write, y, outs[idx]))
            return (jax.lax.ppermute(y, axis_name, perm), outs), None

        # the ring buffer and the outputs vary per stage from the first step
        carry0 = jax.lax.pcast((jnp.zeros(xs.shape[1:], xs.dtype),
                                jnp.zeros_like(xs)), axis_name, to="varying")
        (_, outs), _ = jax.lax.scan(step, carry0,
                                    jnp.arange(m + n_stages - 1))
        # only the last stage holds results; psum broadcasts (others are 0)
        last = (stage == n_stages - 1)
        return jax.lax.psum(jnp.where(last, outs, jnp.zeros_like(outs)),
                            axis_name)

    return jax.shard_map(stage_local, mesh=mesh,
                         in_specs=(P(axis_name), P()), out_specs=P())

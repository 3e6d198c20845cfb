"""GPipe-style pipeline parallelism over ``jax.lax.ppermute`` (optional PP).

Not enabled in the default 40-cell dry-run (TP+DP suffice for memory at the
assigned model sizes — see EXPERIMENTS.md memory analysis); provided for
deployments that need a 4th axis at >8B scale.

Schedule: classic GPipe fill-drain over M microbatches and S stages inside
a shard_map over the ``pipe`` mesh axis.  Each step every stage processes
one microbatch (garbage during fill/drain, masked) and ppermutes its
activation to the next stage; total steps = M + S - 1, bubble fraction
(S-1)/(M+S-1).

    fn = pipeline_apply(stage_fn, mesh, axis="pipe", microbatches=M)
    y = fn(stacked_stage_params, x)       # x [B, ...] -> y [B, ...]

``stage_fn(stage_params, x) -> x`` is the per-stage computation (e.g. a
block of transformer layers); ``stacked_stage_params`` has a leading [S]
dim sharded over ``pipe``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn, mesh, *, axis: str = "pipe",
                   microbatches: int | None = None):
    """Build a pipelined apply over the ``axis`` mesh dimension."""
    S = mesh.shape[axis]

    def apply(stage_params, x):
        B = x.shape[0]
        M = microbatches or S
        assert B % M == 0, (B, M)
        mb = B // M

        def island(params_l, x_l):
            # params_l: [1, ...] this stage's params; x_l: FULL batch
            # (replicated input; stage 0 feeds microbatches in, stage S-1
            # collects outputs)
            params_local = jax.tree.map(lambda p: p[0], params_l)
            sid = jax.lax.axis_index(axis)
            xs = x_l.reshape(M, mb, *x_l.shape[1:])
            state = jnp.zeros_like(xs[0])          # stage input register
            outs = jnp.zeros_like(xs)

            def step(carry, t):
                state, outs = carry
                # stage 0 loads microbatch t (if in range)
                feed = jnp.where(t < M, t, 0)
                state = jnp.where(sid == 0, xs[feed], state)
                y = stage_fn(params_local, state)
                # last stage stores its result at slot t - (S - 1)
                slot = jnp.clip(t - (S - 1), 0, M - 1)
                store = jnp.logical_and(sid == S - 1, t >= S - 1)
                outs = jax.lax.dynamic_update_index_in_dim(
                    outs, jnp.where(store, y, outs[slot]), slot, 0)
                # hand activation to the next stage
                state = jax.lax.ppermute(
                    y, axis, [(i, (i + 1) % S) for i in range(S)])
                return (state, outs), None

            (state, outs), _ = jax.lax.scan(
                step, (state, outs), jnp.arange(M + S - 1))
            # only the last stage holds real outputs; psum-broadcast them
            outs = jnp.where(sid == S - 1, outs, 0.0)
            outs = jax.lax.psum(outs, axis)
            return outs.reshape(B, *x.shape[1:])

        pspec = jax.tree.map(lambda _: P(axis), stage_params)
        return jax.shard_map(
            island, mesh=mesh,
            in_specs=(pspec, P()),
            out_specs=P(),
            check_vma=False,
        )(stage_params, x)

    return apply

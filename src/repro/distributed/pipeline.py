"""GPipe-style pipeline parallelism over a mesh axis (e.g. "pod").

``pipeline_apply`` runs S stages over M microbatches in S+M-1 ticks via
``shard_map`` + ``collective_permute`` hand-off: stage s computes
microbatch m at tick s+m, passing activations ring-wise. Bubble fraction
(S-1)/(S+M-1) — choose M >= 4S in production. The jamba/llava-scale
models map their layer groups onto stages with this scheduler; the unit
test validates exact equality with the sequential stack.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.utils.jax_compat import pvary, shard_map


def pipeline_apply(params_stacked, x_mb, stage_fn, mesh, axis: str = "pod"):
    """params_stacked: pytree with leading dim = n_stages (sharded on axis).
    x_mb: [M, mb, ...] microbatched input (replicated). Returns [M, mb, ...]
    after all stages, computed with the pipelined schedule."""
    S = mesh.shape[axis]
    M = x_mb.shape[0]

    pspec_params = jax.tree_util.tree_map(lambda _: P(axis), params_stacked)

    @partial(shard_map, mesh=mesh,
             in_specs=(pspec_params, P()), out_specs=P())
    def run(params_local, x_all):
        # params_local leaves: [1, ...] — this device's stage
        p = jax.tree_util.tree_map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(axis)
        T = S + M - 1
        buf = jnp.zeros_like(x_all[0])          # current inbound activation
        outs = jnp.zeros_like(x_all)
        # carries become device-varying after the ppermute; mark them so
        buf = pvary(buf, (axis,))
        outs = pvary(outs, (axis,))

        def tick(carry, t):
            buf, outs = carry
            m = t - sid                          # microbatch index at this stage
            active = (m >= 0) & (m < M)
            x_in = jnp.where(sid == 0,
                             x_all[jnp.clip(t, 0, M - 1)], buf)
            y = stage_fn(p, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage writes its finished microbatch
            outs = jnp.where((sid == S - 1) & active,
                             outs.at[jnp.clip(m, 0, M - 1)].set(y), outs)
            # ring hand-off to the next stage
            buf = jax.lax.ppermute(y, axis, [(i, (i + 1) % S) for i in range(S)])
            return (buf, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(T))
        # every device returns the same gathered result: sum over stages
        # (only the last stage wrote non-zeros)
        outs = jax.lax.psum(outs, axis)
        return outs

    return run(params_stacked, x_mb)


def sequential_apply(params_stacked, x_mb, stage_fn):
    """Reference: run all stages sequentially over all microbatches."""
    def one_mb(x):
        def body(x, p):
            return stage_fn(p, x), None
        x, _ = jax.lax.scan(body, x, params_stacked)
        return x
    return jax.vmap(one_mb)(x_mb)

"""Pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis.

A `shard_map`-based schedule: each stage owns a contiguous slice of layers;
activations flow stage->stage via `collective_permute` ring steps.  With M
microbatches and S stages the schedule runs M+S-1 ticks; each tick every
stage applies its layer block to the microbatch it holds, then shifts.

The production configs use DP x TP (+pod DP) — PP is the config option for
depth-dominated models (deepseek-67b 95L) where it converts the FSDP
all-gather traffic into point-to-point transfers; see EXPERIMENTS.md §Perf
for where it wins and where it doesn't.  Correctness is tested on a small
mesh in tests/test_pipeline.py (pipeline == sequential execution, bit-close).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_forward(layer_fn: Callable, params_stacked, x_microbatches,
                     mesh: Mesh, stage_axis: str = "stage"):
    """Run a pipelined forward.

    layer_fn(params_slice, x) -> x          (one stage's layer block)
    params_stacked: pytree with leading dim = n_stages (sharded over
                    ``stage_axis``)
    x_microbatches: [n_micro, mb, ...] activations (replicated)

    Returns [n_micro, mb, ...] outputs.
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x_microbatches.shape[0]
    ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(params_loc, xs):
        # params_loc: this stage's params (leading dim 1) — squeeze
        p_loc = jax.tree.map(lambda a: a[0], params_loc)
        stage = jax.lax.axis_index(stage_axis)
        mb_shape = xs.shape[1:]
        buf = jnp.zeros(mb_shape, xs.dtype)              # activation in flight
        outs = jnp.zeros_like(xs)
        # mark carries as device-varying (they diverge across stages after
        # the first ppermute) so scan's carry types stay consistent
        buf = jax.lax.pcast(buf, (stage_axis,), to="varying")
        outs = jax.lax.pcast(outs, (stage_axis,), to="varying")

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any left)
            feed = jnp.where(t < n_micro, t, n_micro - 1)
            incoming = xs[feed]
            buf = jnp.where(stage == 0,
                            jnp.where(t < n_micro, incoming, buf), buf)
            # every stage processes what it holds
            buf = layer_fn(p_loc, buf)
            # last stage emits microbatch t - (S-1)
            out_idx = t - (n_stages - 1)
            safe = jnp.clip(out_idx, 0, n_micro - 1)
            emit = (stage == n_stages - 1) & (out_idx >= 0)
            outs = jnp.where(
                emit,
                jax.lax.dynamic_update_slice(
                    outs, buf[None].astype(outs.dtype),
                    (safe,) + (0,) * len(mb_shape)),
                outs)
            # shift ring: stage i -> i+1
            buf = jax.lax.ppermute(buf, stage_axis, perm)
            # ppermute moved our buf away and brought the previous stage's in;
            # stage 0's incoming slot is overwritten next tick by the feed.
            return (buf, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(stage_axis), params_stacked),
                  P()),
        out_specs=P(),
    )(params_stacked, x_microbatches)


def sequential_reference(layer_fn, params_stacked, x_microbatches):
    """Oracle: apply all stages sequentially to each microbatch."""
    n_stages = jax.tree.leaves(params_stacked)[0].shape[0]

    def run_one(x):
        for s in range(n_stages):
            p = jax.tree.map(lambda a: a[s], params_stacked)
            x = layer_fn(p, x)
        return x

    return jax.vmap(run_one)(x_microbatches)

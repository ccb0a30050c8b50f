"""GPipe-style pipeline parallelism over a ``stage`` mesh axis
(DESIGN.md §5), written with shard_map + collective_permute.

The production dry-run meshes use DP x TP (+pod) because every assigned
shape fits without PP; this module provides the PP building block for
deeper-than-HBM models and is unit-tested on small meshes
(tests/test_pipeline.py).

Schedule: classic GPipe.  M microbatches flow through S stages; step t
(0 <= t < M + S - 1) runs stage s on microbatch t - s.  Activations move
stage s -> s+1 through one ``collective_permute`` per step (forward-shift
by one along the stage axis).  Each device holds only its stage's layer
stack; bubbles are the usual (S-1)/(M+S-1) fraction.

The layer function is arbitrary (it may itself be TP-sharded on an inner
mesh axis) — the pipeline composes with the rest of the sharding plan.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(layer_fn: Callable, params_stacked, x_microbatches, *,
                   mesh, stage_axis: str = "stage"):
    """Run a GPipe forward pass.

    layer_fn(stage_params, x) -> x        (applied once per stage)
    params_stacked: pytree with leading dim = n_stages (stage-sharded).
    x_microbatches: (M, mb, ...) microbatched input, replicated over the
        stage axis.
    Returns (M, mb, ...) outputs (replicated over the stage axis).
    """
    n_stages = mesh.shape[stage_axis]

    def stage_prog(params, xs):
        # params: this stage's slice (leading dim 1); xs: all microbatches
        sp = jax.tree.map(lambda p: p[0], params)
        sid = jax.lax.axis_index(stage_axis)
        M = xs.shape[0]
        T = M + n_stages - 1
        buf = jnp.zeros_like(xs[0])               # current activation
        outs = jnp.zeros_like(xs)
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def step(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range)
            mb_in = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            buf = jnp.where(sid == 0,
                            jnp.where(t < M, mb_in, jnp.zeros_like(buf)),
                            buf)
            # every stage processes what it holds
            y = layer_fn(sp, buf)
            # last stage emits microbatch t - (S-1) (if in range)
            emit_idx = t - (n_stages - 1)
            do_emit = (sid == n_stages - 1) & (emit_idx >= 0)
            outs = jax.lax.cond(
                do_emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(emit_idx, 0), 0),
                lambda o: o, outs)
            # shift activations forward one stage
            buf = jax.lax.ppermute(y, stage_axis, fwd)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(step, (buf, outs), jnp.arange(T))
        # replicate results to all stages (only the last stage holds them;
        # masked psum acts as a broadcast)
        outs = jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, stage_axis)
        return outs

    return jax.shard_map(
        stage_prog, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False,
    )(params_stacked, x_microbatches)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe bubble overhead: (S-1) / (M+S-1)."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)

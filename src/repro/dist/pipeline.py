"""Pipeline parallelism: GPipe-style microbatch schedule over a ``stage``
mesh axis.

Layers are split contiguously over stages (``stack_stage_params``); each
device runs its stage's layer slice and passes activations to the next stage
with ``ppermute``. The schedule is the classic fill/drain loop: with M
microbatches and S stages it runs M + S - 1 ticks, every stage computing on
every tick (warm-up/drain ticks produce garbage that is masked out by tick
index, which keeps the loop body branch-free and scan-able).

This is the third parallelism axis next to data (batch) and model (tensor):
a pipeline task spans ``S`` devices with per-device memory ~1/S of the layer
stack — exactly the multi-chip ``ResourceVector.chips > 1`` workloads the MGB
schedulers place.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def stack_stage_params(params: Any, n_stages: int) -> Any:
    """Reshape each leaf's leading layer dim [L, ...] -> [S, L // S, ...]
    (stage s gets the contiguous layer slice [s * L/S, (s+1) * L/S))."""

    def split(w):
        L = w.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return w.reshape((n_stages, L // n_stages) + w.shape[1:])

    return jax.tree_util.tree_map(split, params)


def make_pipeline_forward(layer_fn: Callable, mesh, *, n_micro: int,
                          axis: str = None):
    """Build ``pipe(stage_params, x) -> y`` running ``layer_fn`` over a
    pipeline of ``mesh.shape[axis]`` stages with ``n_micro`` microbatches.

    ``layer_fn(stage_params_slice, x)`` applies one stage's layer slice to a
    microbatch and must be shape-preserving in ``x``. ``stage_params`` is the
    output of ``stack_stage_params``; ``x`` is [B, ...] with B % n_micro == 0.
    """
    axis = axis or mesh.axis_names[0]
    n_stages = mesh.shape[axis]

    def pipe(stage_params, x):
        batch = x.shape[0]
        assert batch % n_micro == 0, (batch, n_micro)
        mb = batch // n_micro
        xs = x.reshape((n_micro, mb) + x.shape[1:])

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=P(),
            check_vma=False)
        def _run(sp, xs):
            sp = jax.tree_util.tree_map(lambda w: w[0], sp)  # local slice
            stage = jax.lax.axis_index(axis)
            shift = [(i, (i + 1) % n_stages) for i in range(n_stages)]

            def tick(carry, t):
                recv, outs = carry
                feed = jax.lax.dynamic_index_in_dim(
                    xs, jnp.clip(t, 0, n_micro - 1), keepdims=False)
                inp = jnp.where(stage == 0, feed, recv)
                y = layer_fn(sp, inp)
                # the last stage finishes microbatch t - (S - 1) on tick t
                o_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
                emit = (stage == n_stages - 1) & (t >= n_stages - 1)
                cur = jax.lax.dynamic_index_in_dim(outs, o_idx,
                                                   keepdims=False)
                outs = jax.lax.dynamic_update_index_in_dim(
                    outs, jnp.where(emit, y, cur), o_idx, 0)
                recv = jax.lax.ppermute(y, axis, shift)
                return (recv, outs), None

            carry0 = (jnp.zeros_like(xs[0]), jnp.zeros_like(xs))
            (_, outs), _ = jax.lax.scan(
                tick, carry0, jnp.arange(n_micro + n_stages - 1))
            # results live on the last stage only; replicate them
            outs = jnp.where(stage == n_stages - 1, outs, 0.0)
            return jax.lax.psum(outs, axis)

        ys = _run(stage_params, xs)
        return ys.reshape((batch,) + x.shape[1:])

    return pipe

"""Pallas grouped matmul (MoE expert FFN hot loop).

Rows of x are sorted by expert; ``group_sizes[e]`` rows belong to expert e.
The dense-dispatch einsum in repro.models.moe pads every expert to capacity C
and multiplies zeros; the grouped matmul walks [block_t, D] row tiles and
selects the right expert weight tile per program — compute is O(real tokens),
not O(E * C).

TPU adaptation: CUDA grouped GEMMs schedule one threadblock per (group,
tile); here the grid is (t_blocks, f_blocks) and the expert id of each row
tile is computed outside the kernel (rows are capacity-grouped so a tile
never straddles two experts when block_t divides the capacity). That id
vector is a scalar-prefetch operand: the weight BlockSpec's index map reads
it, so each step DMAs exactly one [D, block_f] tile of its own expert
instead of holding every expert's tile in VMEM. Accumulation is f32 on the
MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(expert_of_ref, x_ref, w_ref, o_ref):
    """One (t_block, f_block) step. x_ref: [block_t, D];
    w_ref: [D, block_f] of the tile's expert."""
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_f", "interpret"))
def moe_gmm(x, w, group_sizes, *, block_t: int = 128, block_f: int = 128,
            interpret: bool = False):
    """x: [T, D] rows sorted by expert; w: [E, D, F]; group_sizes: [E] ints
    summing to T, each a multiple of block_t. Returns [T, F].
    """
    t, d = x.shape
    e, _, f = w.shape
    block_t = min(block_t, t)
    block_f = min(block_f, f)
    assert t % block_t == 0 and f % block_f == 0, (t, block_t, f, block_f)
    nt, nf = t // block_t, f // block_f
    bounds = jnp.cumsum(group_sizes)
    tile_starts = jnp.arange(nt) * block_t
    expert_of_tile = jnp.searchsorted(bounds, tile_starts, side="right"
                                      ).astype(jnp.int32)

    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nt, nf),
            in_specs=[
                pl.BlockSpec((block_t, d), lambda ti, fi, ex: (ti, 0)),
                pl.BlockSpec((None, d, block_f),
                             lambda ti, fi, ex: (ex[ti], 0, fi)),
            ],
            out_specs=pl.BlockSpec((block_t, block_f),
                                   lambda ti, fi, ex: (ti, fi)),
        ),
        out_shape=jax.ShapeDtypeStruct((t, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="moe_gmm",
    )(expert_of_tile, x, w)

"""Pallas chunked selective-scan (the Mamba-1 recurrence hot loop).

h_t = a_t * h_{t-1} + b_t over the sequence, per (batch, channel, state).

TPU adaptation (DESIGN.md §2): the CUDA kernel is a warp-level parallel scan
in shared memory. TPUs have no warp shuffles; the VMEM-native formulation is
a CHUNKED sequential scan. The recurrence is elementwise over (channel,
state), so the wrapper flattens those two axes and views them as
[rows, 128] — full 128-lane vregs whatever the state size (N=16 laid out
last would pad every vreg 8x). The grid is (batch, row blocks, sequence
chunks) with the chunks innermost ("arbitrary"): each step stages one
[chunk, block_r, 128] tile of a and b through VMEM, and the running state
[block_r, 128] is carried across chunks in VMEM scratch. HBM traffic is
read-once/write-once (the pure-XLA associative scan materializes log(S)
intermediate sweeps).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _scan_kernel(a_ref, b_ref, h_all_ref, h_last_ref, h_ref, *, chunk):
    """One (batch, row-block, chunk) step. a/b/h_all_ref: [chunk, R, 128]."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        h = a_ref[t] * h + b_ref[t]
        h_all_ref[t] = h
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    h_ref[...] = h
    h_last_ref[...] = h


@functools.partial(jax.jit,
                   static_argnames=("chunk", "block_r", "interpret"))
def mamba_scan(a, b, *, chunk: int = 64, block_r: int = 32,
               interpret: bool = False):
    """a, b: [B, S, E, N] f32 -> (h_all [B,S,E,N], h_last [B,E,N]).

    Zero initial state (matches the training path; decode uses the one-step
    recurrent update instead). ``E * N`` must be a multiple of 128.
    """
    bsz, s, e, n = a.shape
    assert (e * n) % LANES == 0, (e, n)
    rows = e * n // LANES
    block_r = min(block_r, rows)
    assert rows % block_r == 0, (rows, block_r)
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    a2 = a.astype(jnp.float32).reshape(bsz, s, rows, LANES)
    b2 = b.astype(jnp.float32).reshape(bsz, s, rows, LANES)
    grid = (bsz, rows // block_r, s // chunk)

    def idx(bi, ri, ci):
        return (bi, ci, ri, 0)

    def idx_last(bi, ri, ci):
        return (bi, ri, 0)

    tile = pl.BlockSpec((None, chunk, block_r, LANES), idx)
    h_all, h_last = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=grid,
        in_specs=[tile, tile],
        out_specs=[tile,
                   pl.BlockSpec((None, block_r, LANES), idx_last)],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((bsz, rows, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_r, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mamba_scan",
    )(a2, b2)
    return h_all.reshape(bsz, s, e, n), h_last.reshape(bsz, e, n)

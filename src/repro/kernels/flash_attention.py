"""Pallas TPU flash attention: blocked online-softmax with GQA, sliding
window, and logit softcap.

TPU adaptation (DESIGN.md §2): the CUDA FlashAttention tiles over shared
memory per SM; here BlockSpec stages one [block_q, d] query tile and one
[block_k, d] K/V tile of one KV head through VMEM per grid step. The K
blocks are the innermost ("arbitrary") grid axis, so VMEM holds two tiles
whatever the sequence length, and the online-softmax state (acc, m, l)
lives in VMEM scratch across them. Causal pruning skips K blocks past the
diagonal and the sliding window skips blocks left of the window: a skipped
step computes nothing, and its K/V index is clamped to the last live block,
so the pipeline does not fetch it either.

Layout: the grid is (batch*kv_head, group, q_block, k_block); GQA never
reshapes the head dim (the group rides the grid). The head dim may be any
size (zamba2's 80): a block that spans the whole last dim is legal on TPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "logit_softcap", "block_q",
                              "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    logit_softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: [B, Hq, Sq, D]; k, v: [B, Hkv, Sk, D] -> [B, Hq, Sq, D]."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, block_q, sk, block_k)
    nk = sk // block_k
    grid = (b * hkv, g, sq // block_q, nk)

    def live_range(qi):
        """[lo, hi) K blocks a q block can see: causal upper bound at the
        diagonal, window lower bound left of the oldest visible key."""
        q_start = qi * block_q
        hi = (jnp.minimum((q_start + block_q + block_k - 1) // block_k, nk)
              if causal else nk)
        lo = jnp.maximum((q_start - window) // block_k, 0) if window else 0
        return lo, hi

    def q_index(bh, gi, qi, ki):
        return (bh // hkv, (bh % hkv) * g + gi, qi, 0)

    def kv_index(bh, gi, qi, ki):
        lo, hi = live_range(qi)
        return (bh // hkv, bh % hkv, jnp.clip(ki, lo, hi - 1), 0)

    def kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
        qi, ki = pl.program_id(2), pl.program_id(3)

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        lo, hi = live_range(qi)

        @pl.when((ki >= lo) & (ki < hi))
        def _step():
            s = jax.lax.dot_general(
                q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [bq, bk] MXU
            if logit_softcap:
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = jnp.ones((block_q, block_k), jnp.bool_)
            if causal:
                mask &= q_pos >= k_pos
            if window:
                mask &= (q_pos - k_pos) < window
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            vb = v_ref[...]
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [bq, d] MXU
            m_ref[...] = m_new

        @pl.when(ki == nk - 1)
        def _finish():
            o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                          ).astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d), q_index),
            pl.BlockSpec((None, None, block_k, d), kv_index),
            pl.BlockSpec((None, None, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d), q_index),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)

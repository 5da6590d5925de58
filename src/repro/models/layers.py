"""Core transformer layers: RMSNorm, RoPE, GQA attention (full / sliding-window /
softcap / bias), memory-efficient chunked ("flash") attention in pure jnp, and MLP
variants (silu-gated, gelu-gated, squared-ReLU).

Everything is purely functional: params are nested dicts of jnp arrays.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    with jax.named_scope("norm"):
        dtype = x.dtype
        x = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
        return out.astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # [head_dim/2]


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, head_dim]; positions: [..., S] (broadcastable).

    Interleaved-pair convention: pairs are ADJACENT lanes (2i, 2i+1), so a
    head_dim sharded over the ``model`` mesh axis never splits a rotation pair
    across shards (halved-dim rope forces a cross-shard reshuffle per layer —
    observed as SPMD "involuntary full rematerialization").
    """
    half = x.shape[-1] // 2
    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    xr = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    x1, x2 = xr[..., 0], xr[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    return cap * jnp.tanh(x / cap) if cap else x


# ---------------------------------------------------------------------------
# Attention (jnp reference + chunked flash)
# ---------------------------------------------------------------------------

def _repeat_kv(k: jax.Array, n_q: int) -> jax.Array:
    """[B, Hkv, S, D] -> [B, Hq, S, D] by repeating each KV head.

    GQA via broadcast of the (model-axis-replicated) KV heads keeps the query
    heads dim intact, so its ``model`` sharding survives the attention einsums
    with zero resharding (a q reshape to [Hkv, G] splits the sharded dim).
    """
    b, hkv, s, d = k.shape
    if hkv == n_q:
        return k
    k = jnp.broadcast_to(k[:, :, None], (b, hkv, n_q // hkv, s, d))
    return k.reshape(b, n_q, s, d)


def attention_mask(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
                   window, k_len: Optional[jax.Array] = None) -> jax.Array:
    """Boolean [.., Sq, Sk] mask; True = attend.

    ``window`` may be a python int or a traced scalar (gemma2 alternates the
    window per layer inside a scan); <= 0 means no windowing.
    """
    m = jnp.ones(q_pos.shape + k_pos.shape, dtype=bool)
    delta = q_pos[:, None] - k_pos[None, :]
    if causal:
        m &= delta >= 0
    if window is not None:
        w = jnp.asarray(window)
        m &= (w <= 0) | (delta < w)
    if k_len is not None:
        m &= k_pos[None, :] < k_len
    return m


def naive_attention(q, k, v, *, causal=True, window=0, logit_softcap=0.0,
                    q_offset=0, k_len=None):
    """Oracle attention. q: [B, Hq, Sq, D]; k, v: [B, Hkv, Sk, D]."""
    b, hq, sq, d = q.shape
    sk = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    kr = _repeat_kv(k, hq).astype(jnp.float32)
    vr = _repeat_kv(v, hq).astype(jnp.float32)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kr) * scale
    scores = softcap(scores, logit_softcap)
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(sk)
    mask = attention_mask(q_pos, k_pos, causal=causal, window=window, k_len=k_len)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vr)
    return out.astype(q.dtype)


def flash_attention_jnp(q, k, v, *, causal=True, window=0, logit_softcap=0.0,
                        q_offset=0, block_k: int = 512):
    """Memory-efficient attention: lax.scan over KV blocks with online softmax.

    Never materialises the [Sq, Sk] score matrix for the full sequence — peak
    live memory is O(Sq * block_k). This is the production train/prefill path
    (and the shape-semantics model for the Pallas kernel in repro.kernels).
    """
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    if sk % block_k:
        pad = block_k - sk % block_k
        kpad = [(0, 0), (0, 0), (0, pad), (0, 0)]
        k = jnp.pad(k, kpad)
        v = jnp.pad(v, kpad)
        sk_p = sk + pad
    else:
        sk_p = sk
    nblocks = sk_p // block_k
    scale = 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32)  # [B,Hq,Sq,D]
    q_pos = q_offset + jnp.arange(sq)

    kb = k.reshape(b, hkv, nblocks, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, hkv, nblocks, block_k, d).transpose(2, 0, 1, 3, 4)

    def body(carry, blk):
        acc, m_prev, l_prev, j = carry
        kj, vj = blk  # [B,Hkv,block_k,D]
        kj = _repeat_kv(kj, hq).astype(jnp.float32)
        vj = _repeat_kv(vj, hq).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj) * scale
        s = softcap(s, logit_softcap)
        k_pos = j * block_k + jnp.arange(block_k)
        mask = attention_mask(q_pos, k_pos, causal=causal, window=window,
                              k_len=jnp.asarray(sk))
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vj)
        return (acc, m_new, l_new, j + 1), None

    acc0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    (acc, _, l, _), _ = jax.lax.scan(body, (acc0, m0, l0, 0), (kb, vb))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def _flash_fwd_scan(q, k, v, window, *, causal, logit_softcap, q_offset,
                    block_k, sk_valid):
    """Online-softmax forward over KV blocks; returns (o f32, lse f32)."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    sk_p = k.shape[2]
    nblocks = sk_p // block_k
    scale = 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32)
    q_pos = q_offset + jnp.arange(sq)
    kb = k.reshape(b, hkv, nblocks, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, hkv, nblocks, block_k, d).transpose(2, 0, 1, 3, 4)

    def body(carry, blk):
        acc, m_prev, l_prev, j = carry
        kj, vj = blk
        kj = _repeat_kv(kj, hq).astype(jnp.float32)
        vj = _repeat_kv(vj, hq).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj) * scale
        s = softcap(s, logit_softcap)
        k_pos = j * block_k + jnp.arange(block_k)
        mask = attention_mask(q_pos, k_pos, causal=causal, window=window,
                              k_len=jnp.asarray(sk_valid))
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vj)
        return (acc, m_new, l_new, j + 1), None

    acc0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    (acc, m, l, _), _ = jax.lax.scan(body, (acc0, m0, l0, 0), (kb, vb))
    l_safe = jnp.maximum(l, 1e-30)
    o = acc / l_safe[..., None]
    lse = m + jnp.log(l_safe)
    return o, lse


import functools


@functools.lru_cache(maxsize=256)
def _make_flash_cvjp(causal: bool, logit_softcap: float, q_offset: int,
                     block_k: int, sk_valid: int):
    """Flash attention with RECOMPUTE backward (custom_vjp).

    Plain AD of the forward scan stacks the [B,H,Sq,block_k] probability
    blocks over all KV blocks for the transpose pass — observed 11 GB/device
    at gemma2 train_4k. The FlashAttention backward instead saves only
    (q, k, v, o, lse) and regenerates each block's scores in the reverse
    sweep. ``window`` stays an OPERAND (gemma2 alternates it per layer inside
    a scan, so it can be a tracer).
    """

    @jax.custom_vjp
    def flash(q, k, v, window):
        o, _ = _flash_fwd_scan(q, k, v, window, causal=causal,
                               logit_softcap=logit_softcap, q_offset=q_offset,
                               block_k=block_k, sk_valid=sk_valid)
        return o.astype(q.dtype)

    def fwd(q, k, v, window):
        o, lse = _flash_fwd_scan(q, k, v, window, causal=causal,
                                 logit_softcap=logit_softcap,
                                 q_offset=q_offset, block_k=block_k,
                                 sk_valid=sk_valid)
        o16 = o.astype(q.dtype)
        return o16, (q, k, v, window, o16, lse)

    def bwd(res, do):
        q, k, v, window, o, lse = res
        b, hq, sq, d = q.shape
        hkv = k.shape[1]
        g = hq // hkv
        sk_p = k.shape[2]
        nblocks = sk_p // block_k
        scale = 1.0 / math.sqrt(d)
        qf = q.astype(jnp.float32)
        dof = do.astype(jnp.float32)
        of = o.astype(jnp.float32)
        delta = jnp.sum(dof * of, axis=-1)  # [B,Hq,Sq]
        q_pos = q_offset + jnp.arange(sq)
        kb = k.reshape(b, hkv, nblocks, block_k, d).transpose(2, 0, 1, 3, 4)
        vb = v.reshape(b, hkv, nblocks, block_k, d).transpose(2, 0, 1, 3, 4)

        def body(dq, blk):
            kj, vj, j = blk
            kjr = _repeat_kv(kj, hq).astype(jnp.float32)
            vjr = _repeat_kv(vj, hq).astype(jnp.float32)
            s_pre = jnp.einsum("bhqd,bhkd->bhqk", qf, kjr) * scale
            s = softcap(s_pre, logit_softcap)
            k_pos = j * block_k + jnp.arange(block_k)
            mask = attention_mask(q_pos, k_pos, causal=causal, window=window,
                                  k_len=jnp.asarray(sk_valid))
            s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lse[..., None])                      # [B,Hq,Sq,K]
            dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
            dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vjr)
            ds = p * (dp - delta[..., None])
            if logit_softcap:
                ds = ds * (1.0 - jnp.square(s / logit_softcap))
            ds = jnp.where(mask, ds, 0.0)
            dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kjr) * scale
            dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * scale
            # fold grouped-query heads back onto KV heads
            dkh = dk.reshape(b, hkv, g, block_k, d).sum(axis=2)
            dvh = dv.reshape(b, hkv, g, block_k, d).sum(axis=2)
            return dq, (dkh, dvh)

        dq0 = jnp.zeros((b, hq, sq, d), jnp.float32)
        dq, (dks, dvs) = jax.lax.scan(body, dq0,
                                      (kb, vb, jnp.arange(nblocks)))
        dk = dks.transpose(1, 2, 0, 3, 4).reshape(b, hkv, sk_p, d)
        dv = dvs.transpose(1, 2, 0, 3, 4).reshape(b, hkv, sk_p, d)
        dwin = np.zeros((), jax.dtypes.float0)
        # pin cotangent head sharding: custom_vjp hides the forward pins
        # from GSPMD, and unpinned dq/dk/dv make the wq/wk/wv gradient
        # einsums produce UNSHARDED f32 dW (1 GB/layer/device at llama3)
        from repro.dist.sharding import constrain
        dq = constrain(dq, "batch", "model", None, None)
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
                dwin)

    flash.defvjp(fwd, bwd)
    return flash


def flash_attention_cvjp(q, k, v, *, causal=True, window=0, logit_softcap=0.0,
                         q_offset=0, block_k: int = 512):
    """Production flash attention: memory-efficient forward AND backward."""
    sk = k.shape[2]
    if sk % block_k:
        pad = block_k - sk % block_k
        kpad = [(0, 0), (0, 0), (0, pad), (0, 0)]
        k = jnp.pad(k, kpad)
        v = jnp.pad(v, kpad)
    fn = _make_flash_cvjp(causal, float(logit_softcap), int(q_offset),
                          int(min(block_k, k.shape[2])), int(sk))
    win = jnp.asarray(-1 if window is None else window, jnp.int32)
    return fn(q, k, v, win)


def _decode_valid_mask(smax, cache_len, window):
    """[B or 1, Smax] bool mask of attendable cache slots. ``cache_len`` may
    be a scalar (whole batch at one position — the classic decode loop) or a
    per-row [B] vector (continuous batching: each resident request sits at
    its own position)."""
    cl = jnp.reshape(jnp.asarray(cache_len), (-1, 1))  # [B or 1, 1]
    k_pos = jnp.arange(smax)[None, :]                  # [1, Smax]
    valid = k_pos < cl
    if window is not None:
        w = jnp.asarray(window)
        valid &= (w <= 0) | (k_pos >= cl - w)
    return valid


def decode_attention(q, k_cache, v_cache, cache_len, *, window=0,
                     logit_softcap=0.0):
    """One-token decode. q: [B, Hq, 1, D]; caches: [B, Hkv, Smax, D].

    ``cache_len`` is the number of valid cache entries (the new token's K/V
    must already be written at position cache_len - 1) — a scalar, or a [B]
    vector when rows of a continuously-batched decode sit at different
    sequence positions.

    GQA is contracted GROUPED — q reshaped to [B, Hkv, G, D] — so the KV
    cache is never materialized repeated to Hq heads, and the einsums read
    the cache in its stored dtype with f32 ACCUMULATION
    (preferred_element_type) instead of an f32 copy. At llama3 decode_32k
    the old path peaked 382 GB/device; this one reads the cache once.
    """
    b, hq, _, d = q.shape
    hkv, smax = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    qg = q[:, :, 0, :].reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = softcap(s, logit_softcap)
    valid = _decode_valid_mask(smax, cache_len, window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, 1, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# int8 KV cache (beyond-paper: halves the decode task's HBM footprint)
# ---------------------------------------------------------------------------

def quantize_kv(x: jax.Array, scale_dtype=jnp.bfloat16):
    """x: [..., D] -> (int8 codes [..., D], scales [...]).

    Per-(position, head) absmax scaling: k = k_q * scale, exact within one
    int8 ulp per lane. D stays contiguous so the dequant fuses into the
    attention contraction's operand load on TPU.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    return codes, scale.astype(scale_dtype)


def decode_attention_q8(q, k_q, k_s, v_q, v_s, cache_len, *, window=0,
                        logit_softcap=0.0):
    """One-token decode over an int8 cache.

    q: [B, Hq, 1, D]; k_q/v_q: int8 [B, Hkv, Smax, D]; k_s/v_s: [B, Hkv,
    Smax]. The scales factor OUT of the contractions —
    ``q·k = (q·k_q)·k_s`` and ``Σ p·v = Σ (p·v_s)·v_q`` — so the int8 codes
    are the only cache-sized operand either einsum reads.
    """
    b, hq, _, d = q.shape
    hkv, smax = k_q.shape[1], k_q.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    qg = q[:, :, 0, :].reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k_q.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    s = s * k_s[:, :, None, :].astype(jnp.float32) * scale
    s = softcap(s, logit_softcap)
    valid = _decode_valid_mask(smax, cache_len, window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    pv = (p * v_s[:, :, None, :].astype(jnp.float32)).astype(q.dtype)
    out = jnp.einsum("bhgk,bhkd->bhgd", pv, v_q.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, 1, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_apply(p: dict, x: jax.Array, act: str) -> jax.Array:
    """x: [..., d]. p: {'wi': [d,f], 'wo': [f,d], optional 'wg': [d,f]}.

    The hidden activation is PINNED to [batch->data, ..., f->model]: with
    sequence-sharded residuals GSPMD otherwise keeps S on ``model`` through
    the MLP and computes the wi/wo gradients UNSHARDED (observed 3.25 GB
    f32[53248,16384] per layer per device at llama3 train_4k). Pinning f on
    ``model`` makes the einsums Megatron-TP shaped in both passes.
    """
    from repro.dist.sharding import constrain
    pin = (("batch",) + (None,) * (x.ndim - 2) + ("model",))
    with jax.named_scope("mlp"):
        if act == "silu_gated":
            h = jax.nn.silu(x @ p["wi"]) * (x @ p["wg"])
        elif act == "gelu_gated":
            h = jax.nn.gelu(x @ p["wi"]) * (x @ p["wg"])
        elif act == "squared_relu":
            h = jnp.square(jax.nn.relu(x @ p["wi"]))
        else:
            raise ValueError(f"unknown mlp act {act!r}")
        h = constrain(h, *pin)
        return h @ p["wo"]

"""Plain float32 reference, in ``jax.numpy``: the blocks, and the output
head and training on top of an architecture's ``hidden``.

Each architecture's file (``archs/<program_arch>.py``) composes these
blocks into its ``hidden`` as the configuration file states the
architecture (its ``departures`` list says where that differs from the
published model), with no kernels, cache, chunking or batching tricks:

  * Mamba-2 (SSD) in its quadratic form: y_t = sum_{s<=t} (C_t . B_s)
    exp(sum_{s<r<=t} a_r) dt_s x_s + D x_t;
  * causal softmax attention with interleaved-pair rotary embeddings.

Every matrix product runs at ``highest`` precision. Weights come from
``weights.make_params`` (the same seed gives the same values) and are
raised to float32 one layer at a time inside the layer scans, so the
whole model is never held in float32. Nothing here imports the program.

``quant`` (the control) rounds every weight matrix to float8 (e4m3, one
scale per output column) before use: the precision one step below the
configuration's bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import arch

F32 = jnp.float32


def _w(x, quant: bool):
    """A weight in float32; with ``quant`` rounded through float8 e4m3
    with one absmax scale per output column (matrices only)."""
    x = x.astype(F32)
    if not quant or x.ndim < 2:
        return x
    amax = jnp.max(jnp.abs(x), axis=tuple(range(x.ndim - 1)), keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(F32))


def silu(x):
    return x * jax.nn.sigmoid(x)


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def causal_conv(x, w, b):
    """Depthwise causal convolution. x: [B, S, C]; w: [C, W]; b: [C]."""
    width = w.shape[-1]
    s = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(xp[:, i:i + s] * w[:, i] for i in range(width)) + b


def rope(x, theta):
    """Rotary embedding on adjacent lane pairs (2i, 2i+1). x: [B, H, S, D]."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(x.shape[2], dtype=F32)[:, None] * freqs   # [S, D/2]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def attention(p, x, cfg, quant):
    """Causal multi-head attention. x: [B, S, d]."""
    wq, wk, wv = (_w(p[k], quant) for k in ("wq", "wk", "wv"))
    q = rope(jnp.einsum("bsd,dhk->bhsk", x, wq), cfg["rope_theta"])
    k = rope(jnp.einsum("bsd,dhk->bhsk", x, wk), cfg["rope_theta"])
    v = jnp.einsum("bsd,dhk->bhsk", x, wv)
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    n = x.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    return jnp.einsum("bhsk,hkd->bsd", o, _w(p["wo"], quant))


def gated_mlp(p, x, quant):
    h = gelu_tanh(x @ _w(p["wi"], quant)) * (x @ _w(p["wg"], quant))
    return h @ _w(p["wo"], quant)


def mamba2(p, x, ssm, eps, quant):
    """Mamba-2 block in the SSD quadratic form. x: [B, S, d]."""
    bsz, s, _ = x.shape
    e = p["out_proj"].shape[0]
    n, ph = ssm["state_dim"], ssm["headdim"]
    nh = e // ph
    zxbcdt = x @ _w(p["in_proj"], quant)
    z, xbc, dt = zxbcdt[..., :e], zxbcdt[..., e:2 * e + 2 * n], \
        zxbcdt[..., 2 * e + 2 * n:]
    dt = softplus(dt + p["dt_bias"].astype(F32))               # [B, S, nh]
    xbc = silu(causal_conv(xbc, p["conv_w"].astype(F32),
                           p["conv_b"].astype(F32)))
    xs, bm, cm = xbc[..., :e], xbc[..., e:e + n], xbc[..., e + n:]
    xh = xs.reshape(bsz, s, nh, ph)
    a = -jnp.exp(p["A_log"].astype(F32)) * dt                  # [B, S, nh]
    cum = jnp.cumsum(a, axis=1)
    seg = cum[:, :, None, :] - cum[:, None, :, :]              # [B, t, s, nh]
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("btn,bsn->bts", cm, bm)
    y = jnp.einsum("bts,btsh,bsh,bshp->bthp", cb, decay, dt, xh)
    y = y + p["D"].astype(F32)[:, None] * xh
    y = y.reshape(bsz, s, e) * silu(z)
    y = rms_norm(y, p["norm"], eps)
    return y @ _w(p["out_proj"], quant)


def logits(params, cfg, tokens, quant=False):
    """Logits [B, S, V] at every position of ``tokens`` [B, S]."""
    with jax.default_matmul_precision("highest"):
        return arch.module(cfg).hidden(params, cfg, tokens, quant) \
            @ _w(params["lm_head"], quant)


def loss(params, cfg, tokens, labels, quant=False):
    """Mean next-token cross-entropy over every position."""
    with jax.default_matmul_precision("highest"):
        lg = arch.module(cfg).hidden(params, cfg, tokens, quant) \
            @ _w(params["lm_head"], quant)
        lse = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
        return jnp.mean(lse - gold)


def adamw_steps(params, cfg, opt, batches, quant=False):
    """Plain AdamW (decoupled decay on matrices, global-norm clipping,
    linear warm-up then cosine decay) over ``batches``, in float32 from the
    given weights (``quant``: the forward pass reads float8 weights).
    Returns (losses, the clipped gradient each step fed the moments, the
    weights after the last step)."""
    p = jax.tree_util.tree_map(lambda x: x.astype(F32), params)
    mu = jax.tree_util.tree_map(jnp.zeros_like, p)
    nu = jax.tree_util.tree_map(jnp.zeros_like, p)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda q, t, y: loss(q, cfg, t, y, quant)))
    losses, clipped = [], []
    for i, (tok, lab) in enumerate(batches, start=1):
        l, g = grad_fn(p, tok, lab)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
        g = jax.tree_util.tree_map(
            lambda x: x * jnp.minimum(1.0, opt["clip_norm"]
                                      / jnp.maximum(gn, 1e-9)), g)
        warm, total = opt["warmup_steps"], opt["total_steps"]
        if i < warm:
            lr = opt["lr"] * i / warm
        else:
            frac = min(max((i - warm) / max(total - warm, 1), 0.0), 1.0)
            lr = opt["lr"] * 0.5 * (1 + math.cos(math.pi * frac))
        b1, b2 = opt["b1"], opt["b2"]
        mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x,
                                    nu, g)

        def upd(w, m, v):
            d = (m / (1 - b1 ** i)) / (jnp.sqrt(v / (1 - b2 ** i)) + opt["eps"])
            if w.ndim >= 2:
                d = d + opt["weight_decay"] * w
            return w - lr * d
        p = jax.tree_util.tree_map(upd, p, mu, nu)
        losses.append(float(l))
        clipped.append(g)
    return losses, clipped, p

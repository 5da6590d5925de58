"""State-space blocks: Mamba-1 (selective scan) and Mamba-2 (SSD).

TPU adaptation notes (DESIGN.md §2): the CUDA reference implementations are
hand-fused recurrent kernels; here the train path uses (a) a *chunked* scan —
``lax.scan`` over sequence chunks carrying the SSM state, with an associative scan
inside each chunk — so peak live memory is O(chunk) not O(S·log S), and (b) for
Mamba-2, the SSD *matmul form*: intra-chunk work becomes [Lc, Lc] einsums that map
onto the MXU, with only the inter-chunk state recurrence left sequential. The Pallas
kernel in ``repro.kernels.mamba_scan`` fuses the Mamba-1 chunk loop.

Decode is a one-token recurrent update over (conv_state, ssm_state).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.models.layers import rms_norm


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: [B, S, C]; w: [C, W]; b: [C]."""
    width = w.shape[-1]
    out = jnp.zeros_like(x)
    for i in range(width):
        shift = width - 1 - i
        xi = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :x.shape[1]]
        out = out + xi * w[:, i]
    return out + b


def conv1d_step(x_t: jax.Array, conv_state: jax.Array, w: jax.Array,
                b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One decode step. x_t: [B, C]; conv_state: [B, W-1, C]."""
    with jax.named_scope("state_write"):
        window = jnp.concatenate([conv_state, x_t[:, None]], axis=1)  # [B,W,C]
    out = jnp.einsum("bwc,cw->bc", window, w) + b
    return out, window[:, 1:]


# ---------------------------------------------------------------------------
# Mamba-1
# ---------------------------------------------------------------------------

def _scan_chunked(a: jax.Array, b: jax.Array, h0: jax.Array, chunk: int):
    """Linear recurrence h_t = a_t h_{t-1} + b_t, chunked.

    a, b: [B, S, ...]; h0: [B, ...]. Returns (h_all [B,S,...], h_last).
    """
    bsz, s = a.shape[:2]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    ar = a.reshape((bsz, nc, chunk) + a.shape[2:])
    br = b.reshape((bsz, nc, chunk) + b.shape[2:])

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, b1 * a2 + b2

    def body(h, xs):
        ac, bc = xs  # [B, chunk, ...]
        pa, pb = jax.lax.associative_scan(combine, (ac, bc), axis=1)
        h_all = pa * h[:, None] + pb
        return h_all[:, -1], h_all

    ar_t = jnp.moveaxis(ar, 1, 0)
    br_t = jnp.moveaxis(br, 1, 0)
    h_last, h_chunks = jax.lax.scan(body, h0, (ar_t, br_t))
    h_all = jnp.moveaxis(h_chunks, 0, 1).reshape((bsz, s) + a.shape[2:])
    return h_all, h_last


def mamba1_apply(p: dict, x: jax.Array, cfg: SSMConfig, *, chunk: int = 256,
                 return_state: bool = False):
    """Mamba-1 block. x: [B, S, d] -> [B, S, d] (+ final decode state)."""
    bsz, s, d = x.shape
    e = p["A_log"].shape[0]
    n = cfg.state_dim
    xz = x @ p["in_proj"]  # [B,S,2e]
    xs, z = jnp.split(xz, 2, axis=-1)
    conv_tail = xs[:, -(cfg.conv_width - 1):]  # [B, W-1, e] pre-activation
    xs = jax.nn.silu(causal_conv1d(xs, p["conv_w"], p["conv_b"]))
    dt_rank = p["dt_proj_w"].shape[0]
    proj = xs @ p["x_proj"]  # [B,S,dt_rank+2n]
    dt_low, bmat, cmat = jnp.split(proj, [dt_rank, dt_rank + n], axis=-1)
    dt = jax.nn.softplus(dt_low @ p["dt_proj_w"] + p["dt_proj_b"])  # [B,S,e]
    a_cont = -jnp.exp(p["A_log"].astype(jnp.float32))  # [e,n]
    a = jnp.exp(dt[..., None].astype(jnp.float32) * a_cont)        # [B,S,e,n]
    b = (dt * xs)[..., None].astype(jnp.float32) * bmat[..., None, :].astype(jnp.float32)
    h, h_last = _scan_chunked(a, b, jnp.zeros((bsz, e, n), jnp.float32), chunk)
    y = jnp.einsum("bsen,bsn->bse", h, cmat.astype(jnp.float32))
    y = y + xs.astype(jnp.float32) * p["D"]
    y = y * jax.nn.silu(z).astype(jnp.float32)
    # cast BEFORE out_proj so bf16 params keep the residual stream bf16
    out = y.astype(x.dtype) @ p["out_proj"]
    if return_state:
        return out, {"conv": conv_tail, "ssm": h_last}
    return out


def mamba1_decode_step(p: dict, x_t: jax.Array, state: dict, cfg: SSMConfig):
    """x_t: [B, d]. state: {'conv': [B, W-1, e], 'ssm': [B, e, n]}."""
    n = cfg.state_dim
    xz = x_t @ p["in_proj"]
    xs, z = jnp.split(xz, 2, axis=-1)
    xs, conv_state = conv1d_step(xs, state["conv"], p["conv_w"], p["conv_b"])
    xs = jax.nn.silu(xs)
    dt_rank = p["dt_proj_w"].shape[0]
    proj = xs @ p["x_proj"]
    dt_low, bmat, cmat = jnp.split(proj, [dt_rank, dt_rank + n], axis=-1)
    dt = jax.nn.softplus(dt_low @ p["dt_proj_w"] + p["dt_proj_b"])  # [B,e]
    a_cont = -jnp.exp(p["A_log"].astype(jnp.float32))
    a = jnp.exp(dt[..., None].astype(jnp.float32) * a_cont)  # [B,e,n]
    b = (dt * xs)[..., None].astype(jnp.float32) * bmat[:, None, :].astype(jnp.float32)
    with jax.named_scope("state_write"):
        h = a * state["ssm"] + b
    y = jnp.einsum("ben,bn->be", h, cmat.astype(jnp.float32))
    y = (y + xs.astype(jnp.float32) * p["D"]) \
        * jax.nn.silu(z).astype(jnp.float32)
    return y.astype(x_t.dtype) @ p["out_proj"], \
        {"conv": conv_state, "ssm": h}


# ---------------------------------------------------------------------------
# Mamba-2 (SSD, chunked matmul form)
# ---------------------------------------------------------------------------

def _split_m2(p: dict, x: jax.Array, cfg: SSMConfig):
    e = p["out_proj"].shape[0]
    n = cfg.state_dim
    nh = e // cfg.headdim
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [e, e + e + 2 * n], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # [..., nh]
    return z, xbc, dt, e, n, nh


def mamba2_apply(p: dict, x: jax.Array, cfg: SSMConfig, *,
                 return_state: bool = False):
    """Mamba-2 (SSD) block, chunked. x: [B, S, d]."""
    bsz, s, d = x.shape
    z, xbc, dt, e, n, nh = _split_m2(p, x, cfg)
    conv_tail = xbc[:, -(cfg.conv_width - 1):]  # [B, W-1, e+2n]
    xbc = jax.nn.silu(causal_conv1d(xbc, p["conv_w"], p["conv_b"]))
    xs, bmat, cmat = jnp.split(xbc, [e, e + n], axis=-1)
    ph = cfg.headdim
    xh = xs.reshape(bsz, s, nh, ph)
    log_a = (-jnp.exp(p["A_log"].astype(jnp.float32)) * dt.astype(jnp.float32))

    lc = min(cfg.chunk, s)
    assert s % lc == 0, (s, lc)
    nc = s // lc
    xh_c = xh.reshape(bsz, nc, lc, nh, ph)
    dt_c = dt.reshape(bsz, nc, lc, nh).astype(jnp.float32)
    b_c = bmat.reshape(bsz, nc, lc, n).astype(jnp.float32)
    c_c = cmat.reshape(bsz, nc, lc, n).astype(jnp.float32)
    la_c = log_a.reshape(bsz, nc, lc, nh)
    cum = jnp.cumsum(la_c, axis=2)                      # [B,nc,Lc,nh]
    dtx = (dt_c[..., None] * xh_c.astype(jnp.float32))  # [B,nc,Lc,nh,P]

    # intra-chunk (attention-like, MXU-friendly)
    g = jnp.einsum("bcln,bcsn->bcls", c_c, b_c)         # [B,nc,Lc,Lc]
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [B,nc,Lc,Lc,nh]
    causal = jnp.tril(jnp.ones((lc, lc), bool))
    # mask before exp: above the diagonal seg > 0 and exp overflows over a
    # long chunk, and a masked inf still turns the gradient into 0 * inf
    att = jnp.exp(jnp.where(causal[None, None, :, :, None], seg, -jnp.inf)) \
        * g[..., None]
    y_intra = jnp.einsum("bclsh,bcshp->bclhp", att, dtx)

    # chunk state contributions and inter-chunk recurrence
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)     # [B,nc,Lc,nh]
    s_c = jnp.einsum("bcsn,bcsh,bcshp->bchpn", b_c, decay_to_end, dtx)
    a_chunk = jnp.exp(cum[:, :, -1, :])                 # [B,nc,nh]

    def body(h, xs_):
        a_k, s_k = xs_  # [B,nh], [B,nh,P,N]
        h_new = h * a_k[..., None, None] + s_k
        return h_new, h  # emit state *entering* the chunk

    h0 = jnp.zeros((bsz, nh, ph, n), jnp.float32)
    h_last, h_prev = jax.lax.scan(body, h0, (jnp.moveaxis(a_chunk, 1, 0),
                                             jnp.moveaxis(s_c, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)                 # [B,nc,nh,P,N]
    y_inter = jnp.einsum("bcln,bclh,bchpn->bclhp", c_c, jnp.exp(cum), h_prev)

    y = (y_intra + y_inter).reshape(bsz, s, nh, ph)
    y = y + p["D"][:, None] * xh.astype(jnp.float32)
    y = y.reshape(bsz, s, e).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"])
    out = y @ p["out_proj"]
    if return_state:
        return out, {"conv": conv_tail, "ssm": h_last}
    return out


def mamba2_decode_step(p: dict, x_t: jax.Array, state: dict, cfg: SSMConfig):
    """x_t: [B, d]. state: {'conv': [B, W-1, e+2n], 'ssm': [B, nh, P, N]}."""
    bsz, d = x_t.shape
    z, xbc, dt, e, n, nh = _split_m2(p, x_t, cfg)
    xbc, conv_state = conv1d_step(xbc, state["conv"], p["conv_w"], p["conv_b"])
    xbc = jax.nn.silu(xbc)
    xs, bmat, cmat = jnp.split(xbc, [e, e + n], axis=-1)
    ph = cfg.headdim
    xh = xs.reshape(bsz, nh, ph).astype(jnp.float32)
    dt32 = dt.astype(jnp.float32)
    a = jnp.exp(-jnp.exp(p["A_log"].astype(jnp.float32)) * dt32)  # [B,nh]
    dtx = dt32[..., None] * xh                                    # [B,nh,P]
    with jax.named_scope("state_write"):
        h = state["ssm"] * a[..., None, None] \
            + dtx[..., None] * bmat.astype(jnp.float32)[:, None, None, :]
    y = jnp.einsum("bhpn,bn->bhp", h, cmat.astype(jnp.float32))
    y = y + p["D"][:, None] * xh
    y = y.reshape(bsz, e).astype(x_t.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"])
    return y @ p["out_proj"], {"conv": conv_state, "ssm": h}

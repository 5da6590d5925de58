"""One-token decode over model caches, for every architecture family.

Caches:
  * ATTN stacks: KV tensors stacked over layers ``[L, B, Hkv, Smax, hd]``.
    Pure-SWA archs (mixtral) get a ring buffer of size ``min(Smax, window)`` —
    the window is enforced by overwrite, so a 500k-token context costs O(window)
    HBM (this is what makes mixtral long_500k runnable, DESIGN.md §4).
  * SSM (falcon-mamba): conv + SSM recurrent states per layer, O(1) in context.
  * hybrid (zamba2): grouped Mamba-2 states + per-group shared-attention KV.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import ssm as SSM
from repro.models.model import (
    Params, attn_decode_block, logits_from_hidden, _layer_window,
)
from repro.models.moe import moe_apply

Cache = Dict[str, Any]


def uses_ring(cfg: ArchConfig) -> bool:
    return cfg.sliding_window > 0 and not cfg.local_global_alternate


def cache_seq_len(cfg: ArchConfig, max_seq: int) -> int:
    return min(max_seq, cfg.sliding_window) if uses_ring(cfg) else max_seq


def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> Cache:
    hd = cfg.resolved_head_dim if cfg.n_heads else 0
    if cfg.family == "ssm":
        e = cfg.ssm.expand * cfg.d_model
        return {
            "conv": jnp.zeros((cfg.n_layers, batch, cfg.ssm.conv_width - 1, e),
                              dtype),
            "ssm": jnp.zeros((cfg.n_layers, batch, e, cfg.ssm.state_dim),
                             jnp.float32),
        }
    if cfg.family == "hybrid":
        k = cfg.hybrid_shared_every
        g = cfg.n_layers // k
        e = cfg.ssm.expand * cfg.d_model
        n = cfg.ssm.state_dim
        nh = e // cfg.ssm.headdim
        smax = cache_seq_len(cfg, max_seq)
        return {
            "m_conv": jnp.zeros((g, k - 1, batch, cfg.ssm.conv_width - 1,
                                 e + 2 * n), dtype),
            "m_ssm": jnp.zeros((g, k - 1, batch, nh, cfg.ssm.headdim, n),
                               jnp.float32),
            "k": jnp.zeros((g, batch, cfg.n_kv_heads, smax, hd), dtype),
            "v": jnp.zeros((g, batch, cfg.n_kv_heads, smax, hd), dtype),
        }
    smax = cache_seq_len(cfg, max_seq)
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, smax, hd)
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_s": jnp.zeros(shape[:-1], dtype),
            "v_s": jnp.zeros(shape[:-1], dtype),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _put(stack: jax.Array, idx, value: jax.Array) -> jax.Array:
    """``stack`` with ``value`` written in place at the leading index
    ``idx``: a dynamic_update_slice (``.at[idx].set`` lowers to a
    scatter)."""
    value = value.reshape((1,) * len(idx) + value.shape)
    start = tuple(idx) + (0,) * (value.ndim - len(idx))
    return jax.lax.dynamic_update_slice(stack, value.astype(stack.dtype),
                                        start)


def decode_step(params: Params, cfg: ArchConfig, cache: Cache,
                tokens: jax.Array, pos: jax.Array
                ) -> Tuple[jax.Array, Cache]:
    """tokens: [B] int32; pos: scalar int32 (current position, 0-based) or a
    [B] vector when rows decode at independent positions (continuous
    batching — see serve.engine).

    Returns (logits [B, V] f32, updated cache).

    Named scopes mark the model's parts in the compiled program's op_name
    metadata (and so in a device trace): ``embed``, ``norm``, ``mamba``,
    ``state_write`` (the new recurrent state), ``attn``, ``kv_write``,
    ``mlp``, ``logits``. Outside them lies the layer scans' own work:
    slicing the stacked weights and caches, stacking the new ones, copies
    and loop bookkeeping. The hybrid step slices and stacks nothing: its
    reads fuse into the model's ops, and each new state or KV is written
    into its slot under ``state_write`` or ``kv_write``.
    """
    from repro.dist.sharding import constrain
    with jax.named_scope("embed"):
        x = params["embed"][tokens]  # [B, d]
        x = constrain(x, "batch", None)
        if cfg.name.startswith("gemma2"):
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    ring = uses_ring(cfg)

    if cfg.family == "ssm":
        def body(h, xs):
            lp, conv, ssm_state = xs
            conv = jax.lax.optimization_barrier(conv)
            ssm_state = jax.lax.optimization_barrier(ssm_state)
            with jax.named_scope("mamba"):
                y, new = SSM.mamba1_decode_step(
                    lp["mamba"], L.rms_norm(h, lp["norm"]),
                    {"conv": conv, "ssm": ssm_state}, cfg.ssm)
            return h + y, (new["conv"], new["ssm"])
        x, (conv, ssm_state) = jax.lax.scan(
            body, x, (params["layers"], cache["conv"], cache["ssm"]))
        new_cache = {"conv": conv, "ssm": ssm_state}
    elif cfg.family == "hybrid":
        # The scan runs over the group index. Weights are read in place from
        # their stacks; states and caches ride in the carry and are updated
        # in place, under state_write / kv_write since XLA fuses the new
        # state's math into the update. Scanned as xs/ys instead, each
        # group is sliced out of its stack and copied to another layout for
        # the dots, every step. A group's Mamba layers are unrolled: a
        # rolled inner loop reading [g, j] copies the whole in_proj stack
        # to another layout once per step.
        shared = params["shared"]
        gps = params["groups"]

        def group_body(carry, g):
            h, mconv, mssm, kc, vc = carry
            for j in range(cfg.hybrid_shared_every - 1):
                mp = jax.tree_util.tree_map(lambda t: t[g, j], gps["mamba"])
                with jax.named_scope("mamba"):
                    y, new = SSM.mamba2_decode_step(
                        mp, L.rms_norm(h, gps["norm_m"][g, j]),
                        {"conv": mconv[g, j], "ssm": mssm[g, j]}, cfg.ssm)
                    with jax.named_scope("state_write"):
                        mconv = _put(mconv, (g, j), new["conv"])
                        mssm = _put(mssm, (g, j), new["ssm"])
                h = h + y
            with jax.named_scope("attn"):
                a, (kg, vg) = attn_decode_block(
                    shared["attn"],
                    L.rms_norm(h, gps["norm_attn"][g])[:, None], cfg,
                    pos=pos, kcache=kc[g], vcache=vc[g],
                    window=cfg.sliding_window, ring=ring)
                with jax.named_scope("kv_write"):
                    kc = _put(kc, (g,), kg)
                    vc = _put(vc, (g,), vg)
            h = h + a[:, 0]
            m = L.mlp_apply(shared["mlp"], L.rms_norm(h, gps["norm_mlp"][g]),
                            cfg.mlp_act)
            return (h + m, mconv, mssm, kc, vc), None
        (x, mconv, mssm, kc, vc), _ = jax.lax.scan(
            group_body,
            (x, cache["m_conv"], cache["m_ssm"], cache["k"], cache["v"]),
            jnp.arange(cfg.n_layers // cfg.hybrid_shared_every))
        new_cache = {"m_conv": mconv, "m_ssm": mssm, "k": kc, "v": vc}
    else:
        layer_idx = jnp.arange(cfg.n_layers)
        q8 = cfg.kv_cache_dtype == "int8"

        def body(h, xs):
            if q8:
                lp, idx, kc, vc, ks, vs = xs
            else:
                lp, idx, kc, vc = xs
                ks = vs = None
            # barrier: the attention einsums read the cache with f32
            # accumulation; without the barrier XLA hoists that convert out
            # of the layer loop and materializes the WHOLE stacked cache in
            # f32 (observed +20 GB/device at qwen decode_32k)
            kc = jax.lax.optimization_barrier(kc)
            vc = jax.lax.optimization_barrier(vc)
            window = _layer_window(cfg, idx)
            with jax.named_scope("attn"):
                a, kv = attn_decode_block(
                    lp["attn"], L.rms_norm(h, lp["norm1"])[:, None], cfg,
                    pos=pos, kcache=kc, vcache=vc, kscale=ks, vscale=vs,
                    window=window, ring=ring)
            h = h + a[:, 0]
            hn = L.rms_norm(h, lp["norm2"])[:, None]
            if cfg.moe is not None:
                m, _ = moe_apply(lp["moe"], hn, cfg.moe, cfg.mlp_act)
            else:
                m = L.mlp_apply(lp["mlp"], hn, cfg.mlp_act)
            return h + m[:, 0], kv
        if q8:
            x, (kc, vc, ks, vs) = jax.lax.scan(
                body, x, (params["layers"], layer_idx, cache["k"],
                          cache["v"], cache["k_s"], cache["v_s"]))
            new_cache = {"k": kc, "v": vc, "k_s": ks, "v_s": vs}
        else:
            x, (kc, vc) = jax.lax.scan(
                body, x,
                (params["layers"], layer_idx, cache["k"], cache["v"]))
            new_cache = {"k": kc, "v": vc}

    x = L.rms_norm(x, params["final_norm"])
    with jax.named_scope("logits"):
        logits = logits_from_hidden(cfg, params, x[:, None])[:, 0]
    return logits, new_cache


# ---------------------------------------------------------------------------
# Slot-wise cache surgery (continuous batching)
#
# A running decode batch adopts a prefilled request's single-row cache and
# retires finished rows in place: extract slices one row out, insert writes a
# row back (right-padding the sequence axis so a short prefill cache drops
# into a longer resident buffer; slots past the row's cache_len are masked by
# decode_attention, so the zero padding is never attended).
# ---------------------------------------------------------------------------

# per-key (batch_axis, seq_axis or None) for every cache layout produced by
# init_cache across the attn / ssm / hybrid families
CACHE_AXES: Dict[str, Tuple[int, Any]] = {
    "k": (1, 3), "v": (1, 3), "k_s": (1, 3), "v_s": (1, 3),
    "conv": (1, None), "ssm": (1, None),
    "m_conv": (2, None), "m_ssm": (2, None),
}


def cache_rows(cache: Cache) -> int:
    """Batch capacity (number of resident rows) of a decode cache."""
    key = next(iter(cache))
    return cache[key].shape[CACHE_AXES[key][0]]


def cache_extract(cache: Cache, row) -> Cache:
    """Slice out one resident row as a batch-1 cache. ``row`` may be a
    static int or a traced scalar."""
    return {key: jax.lax.dynamic_slice_in_dim(t, row, 1,
                                              axis=CACHE_AXES[key][0])
            for key, t in cache.items()}


def cache_insert(cache: Cache, row_cache: Cache, row) -> Cache:
    """Write a batch-1 ``row_cache`` into resident slot ``row``.

    The row cache's sequence axis may be SHORTER than the resident buffer's
    (e.g. a prompt-length prefill cache joining a max_seq batch, or a
    short-prompt ring): it is right-padded with zeros, which stay masked
    until decode writes them. A LONGER sequence axis is an error — the
    resident buffer cannot hold it.
    """
    out = {}
    for key, t in cache.items():
        bax, sax = CACHE_AXES[key]
        rt = row_cache[key]
        if sax is not None and rt.shape[sax] != t.shape[sax]:
            if rt.shape[sax] > t.shape[sax]:
                raise ValueError(
                    f"cache_insert: row cache {key} seq {rt.shape[sax]} "
                    f"exceeds resident buffer seq {t.shape[sax]}")
            pad = [(0, 0)] * rt.ndim
            pad[sax] = (0, t.shape[sax] - rt.shape[sax])
            rt = jnp.pad(rt, pad)
        out[key] = jax.lax.dynamic_update_slice_in_dim(
            t, rt.astype(t.dtype), row, axis=bax)
    return out


def cache_clear_row(cache: Cache, row) -> Cache:
    """Zero a retired row so stale KV bytes can't leak into a later adopt
    (cheap hygiene; correctness never reads a masked slot)."""
    zeros = {key: jnp.zeros_like(t) for key, t in cache_extract(
        cache, 0 if isinstance(row, int) else row).items()}
    return cache_insert(cache, zeros, row)

"""Composable decoder model covering all assigned architecture families.

Design:
  * params are nested dicts of jnp arrays; layer weights are STACKED on a leading
    [L] (or [G] group) dim and the decoder runs ``lax.scan`` over layers, so the
    lowered HLO is O(1) in depth — critical for 96–126-layer dry-run compiles.
  * families: ATTN stacks (dense/moe/vlm/audio), MAMBA1 stacks (ssm), and the
    zamba2 hybrid (grouped Mamba-2 + shared-weight attention block).
  * ``forward`` handles train/prefill (full sequence); ``decode_step`` handles
    one-token decode over a cache (KV ring-buffer for pure-SWA archs, recurrent
    states for SSM/hybrid).
  * remat: the scan body is wrapped in ``jax.checkpoint`` per config policy.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ATTN, MAMBA1, MAMBA2, SHARED_ATTN
from repro.dist.sharding import constrain
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def _init(key, shape, dtype, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _keys(key, n):
    return list(jax.random.split(key, n))


def _attn_params(key, cfg: ArchConfig, stack: Tuple[int, ...], dtype) -> Params:
    d, h, kv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    ks = _keys(key, 4)
    p = {
        "wq": _init(ks[0], stack + (d, h, hd), dtype, d ** -0.5),
        "wk": _init(ks[1], stack + (d, kv, hd), dtype, d ** -0.5),
        "wv": _init(ks[2], stack + (d, kv, hd), dtype, d ** -0.5),
        "wo": _init(ks[3], stack + (h, hd, d), dtype, (h * hd) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros(stack + (h, hd), dtype)
        p["bk"] = jnp.zeros(stack + (kv, hd), dtype)
        p["bv"] = jnp.zeros(stack + (kv, hd), dtype)
    return p


def _mlp_params(key, cfg: ArchConfig, stack, dtype) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    ks = _keys(key, 3)
    p = {"wi": _init(ks[0], stack + (d, f), dtype),
         "wo": _init(ks[1], stack + (f, d), dtype)}
    if cfg.mlp_act.endswith("gated"):
        p["wg"] = _init(ks[2], stack + (d, f), dtype)
    return p


def _moe_params(key, cfg: ArchConfig, stack, dtype) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    ks = _keys(key, 4)
    p = {"router": _init(ks[0], stack + (d, e), dtype),
         "wi": _init(ks[1], stack + (e, d, f), dtype),
         "wo": _init(ks[2], stack + (e, f, d), dtype)}
    if cfg.mlp_act.endswith("gated"):
        p["wg"] = _init(ks[3], stack + (e, d, f), dtype)
    return p


def _mamba1_params(key, cfg: ArchConfig, stack, dtype) -> Params:
    d = cfg.d_model
    e = cfg.ssm.expand * d
    n = cfg.ssm.state_dim
    w = cfg.ssm.conv_width
    r = max(1, d // 16)  # dt_rank
    ks = _keys(key, 5)
    a_init = jnp.log(jnp.broadcast_to(
        jnp.arange(1, n + 1, dtype=jnp.float32), stack + (e, n)))
    return {
        "in_proj": _init(ks[0], stack + (d, 2 * e), dtype),
        "conv_w": _init(ks[1], stack + (e, w), dtype, 0.2),
        "conv_b": jnp.zeros(stack + (e,), dtype),
        "x_proj": _init(ks[2], stack + (e, r + 2 * n), dtype),
        "dt_proj_w": _init(ks[3], stack + (r, e), dtype),
        "dt_proj_b": jnp.full(stack + (e,), -4.0, dtype),
        "A_log": a_init.astype(jnp.float32),
        "D": jnp.ones(stack + (e,), jnp.float32),
        "out_proj": _init(ks[4], stack + (e, d), dtype),
    }


def _mamba2_params(key, cfg: ArchConfig, stack, dtype) -> Params:
    d = cfg.d_model
    e = cfg.ssm.expand * d
    n = cfg.ssm.state_dim
    w = cfg.ssm.conv_width
    nh = e // cfg.ssm.headdim
    ks = _keys(key, 3)
    return {
        "in_proj": _init(ks[0], stack + (d, 2 * e + 2 * n + nh), dtype),
        "conv_w": _init(ks[1], stack + (e + 2 * n, w), dtype, 0.2),
        "conv_b": jnp.zeros(stack + (e + 2 * n,), dtype),
        "dt_bias": jnp.zeros(stack + (nh,), jnp.float32),
        "A_log": jnp.zeros(stack + (nh,), jnp.float32),
        "D": jnp.ones(stack + (nh,), jnp.float32),
        "norm": jnp.zeros(stack + (e,), dtype),
        "out_proj": _init(ks[2], stack + (e, d), dtype),
    }


def init_params(cfg: ArchConfig, key: jax.Array,
                param_dtype=jnp.float32) -> Params:
    d, v = cfg.d_model, cfg.vocab
    ks = _keys(key, 8)
    params: Params = {"embed": _init(ks[0], (v, d), param_dtype, 1.0)}
    if cfg.family == "hybrid":
        k = cfg.hybrid_shared_every
        assert cfg.n_layers % k == 0, "hybrid needs n_layers % shared_every == 0"
        g = cfg.n_layers // k
        params["groups"] = {
            "mamba": _mamba2_params(ks[1], cfg, (g, k - 1), param_dtype),
            "norm_m": jnp.zeros((g, k - 1, d), param_dtype),
            "norm_attn": jnp.zeros((g, d), param_dtype),
            "norm_mlp": jnp.zeros((g, d), param_dtype),
        }
        params["shared"] = {
            "attn": _attn_params(ks[2], cfg, (), param_dtype),
            "mlp": _mlp_params(ks[3], cfg, (), param_dtype),
        }
    elif cfg.family == "ssm":
        nl = (cfg.n_layers,)
        params["layers"] = {
            "norm": jnp.zeros(nl + (d,), param_dtype),
            "mamba": _mamba1_params(ks[1], cfg, nl, param_dtype),
        }
    else:
        nl = (cfg.n_layers,)
        lp: Params = {
            "norm1": jnp.zeros(nl + (d,), param_dtype),
            "norm2": jnp.zeros(nl + (d,), param_dtype),
            "attn": _attn_params(ks[1], cfg, nl, param_dtype),
        }
        if cfg.moe is not None:
            lp["moe"] = _moe_params(ks[2], cfg, nl, param_dtype)
        else:
            lp["mlp"] = _mlp_params(ks[2], cfg, nl, param_dtype)
        params["layers"] = lp
    params["final_norm"] = jnp.zeros((d,), param_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = _init(ks[4], (d, v), param_dtype)
    return params


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _project_qkv(p: Params, x: jax.Array):
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"][None, :, None, :]
        k = k + p["bk"][None, :, None, :]
        v = v + p["bv"][None, :, None, :]
    # pin heads on `model` so the seq-sharded residual's S->model sharding
    # does not leak into attention (it forces unsharded w[qkv] gradients)
    q = constrain(q, "batch", "model", None, None)
    k = constrain(k, "batch", "model", None, None)
    v = constrain(v, "batch", "model", None, None)
    return q, k, v


def attn_block(p: Params, x: jax.Array, cfg: ArchConfig, *, positions,
               window: int, attn_impl: str, return_kv: bool = False):
    """Full-sequence attention (train/prefill). x: [B, S, d]."""
    q, k, v = _project_qkv(p, x)
    q = L.apply_rope(q, positions[None, None, :], cfg.rope_theta)
    k = L.apply_rope(k, positions[None, None, :], cfg.rope_theta)
    kwargs = dict(causal=True, window=window, logit_softcap=cfg.attn_logit_softcap)
    if attn_impl == "flash":
        o = L.flash_attention_cvjp(q, k, v, **kwargs)
    elif attn_impl == "flash_jnp":
        o = L.flash_attention_jnp(q, k, v, **kwargs)
    elif attn_impl == "naive":
        o = L.naive_attention(q, k, v, **kwargs)
    elif attn_impl == "pallas":
        from repro.kernels import ops as KOPS
        o = KOPS.flash_attention(q, k, v, **kwargs)
    else:
        raise ValueError(attn_impl)
    out = jnp.einsum("bhsk,hkd->bsd", o, p["wo"])
    if return_kv:
        return out, (k, v)
    return out


def attn_decode_block(p: Params, x: jax.Array, cfg: ArchConfig, *, pos,
                      kcache, vcache, window: int, ring: bool,
                      kscale=None, vscale=None):
    """One-token attention. x: [B, 1, d]; caches: [B, Hkv, Smax, D].

    ``pos`` is a scalar (whole batch at one sequence position) or a [B]
    vector (continuous batching: every resident row at its own position —
    the scalar path keeps the cheap contiguous dynamic_update_slice, the
    vector path scatters one slot per row through a one-hot mask).

    When ``kscale``/``vscale`` are given the cache is int8 with
    per-(position, head) scales (cfg.kv_cache_dtype == "int8"). Returns
    (attn_out, updated-cache tuple) — (kc, vc) or (kc, vc, ks, vs).
    """
    q, k, v = _project_qkv(p, x)  # [B,H,1,hd]
    per_row = jnp.ndim(pos) >= 1
    b = x.shape[0]
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    q = L.apply_rope(q, posv[:, None, None], cfg.rope_theta)
    k = L.apply_rope(k, posv[:, None, None], cfg.rope_theta)
    smax = kcache.shape[2]
    slot = (posv % smax) if ring else jnp.minimum(posv, smax - 1)  # [B]
    cache_len = jnp.minimum((posv if per_row else pos) + 1, smax)
    win = 0 if ring else window  # ring enforces the window by overwrite

    if per_row:
        oh = jnp.arange(smax)[None, :] == slot[:, None]  # [B, Smax]

        def write(cache, new):  # new: [B, H, 1, D] or [B, H, 1] (scales)
            mask = oh[:, None, :, None] if cache.ndim == 4 else oh[:, None, :]
            with jax.named_scope("kv_write"):
                return jnp.where(mask, new.astype(cache.dtype), cache)
    else:
        def write(cache, new):
            with jax.named_scope("kv_write"):
                return jax.lax.dynamic_update_slice_in_dim(
                    cache, new.astype(cache.dtype), slot[0], axis=2)

    if kscale is not None:
        k_q, k_s = L.quantize_kv(k, kscale.dtype)
        v_q, v_s = L.quantize_kv(v, vscale.dtype)
        k_q = jax.lax.optimization_barrier(k_q)
        v_q = jax.lax.optimization_barrier(v_q)
        kcache = write(kcache, k_q)
        vcache = write(vcache, v_q)
        kscale = write(kscale, k_s)
        vscale = write(vscale, v_s)
        o = L.decode_attention_q8(q, kcache, kscale, vcache, vscale,
                                  cache_len, window=win,
                                  logit_softcap=cfg.attn_logit_softcap)
        return jnp.einsum("bhsk,hkd->bsd", o, p["wo"]), \
            (kcache, vcache, kscale, vscale)
    # cast + barrier BEFORE the cache write: without the barrier XLA fuses
    # the rope's f32->bf16 convert by converting the ENTIRE cache to f32 for
    # the update instead (observed +20 GB/device at qwen decode_32k)
    k = jax.lax.optimization_barrier(k.astype(kcache.dtype))
    v = jax.lax.optimization_barrier(v.astype(vcache.dtype))
    kcache = write(kcache, k)
    vcache = write(vcache, v)
    o = L.decode_attention(q, kcache, vcache, cache_len, window=win,
                           logit_softcap=cfg.attn_logit_softcap)
    return jnp.einsum("bhsk,hkd->bsd", o, p["wo"]), (kcache, vcache)


def _layer_window(cfg: ArchConfig, layer_idx) -> Any:
    """Per-layer sliding window (gemma2 alternates local/global)."""
    if not cfg.sliding_window:
        return 0
    if cfg.local_global_alternate:
        return jnp.where(layer_idx % 2 == 0, cfg.sliding_window, 0)
    return cfg.sliding_window


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _barrier(x):
    """optimization_barrier on the scan carry: without it XLA hoists the
    rms_norm f32 convert of the ENTIRE stacked saved-residual buffer out of
    the backward loop (observed +39 GB/device at gemma2 train_4k).

    The barrier's own transpose rule puts the same barrier on the
    cotangent, so the backward scan carry is protected from the identical
    hoist without a custom VJP."""
    return jax.lax.optimization_barrier(x)


def _remat(fn, policy: str):
    if policy == "nothing":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, prevent_cse=False,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    if policy == "full":
        # prevent_cse=False is safe (and documented) under lax.scan; the
        # default True wraps saves in barriers that force an extra f32 copy of
        # the whole residual stack (observed +39 GB/device at gemma2 train_4k)
        return jax.checkpoint(fn, prevent_cse=False,
                              policy=jax.checkpoint_policies.nothing_saveable)
    raise ValueError(policy)


def embed_tokens(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array]):
    if cfg.embedding_frontend_stub and "embeds" in batch:
        x = batch["embeds"]  # modality frontend stub: precomputed embeddings
    else:
        x = params["embed"][batch["tokens"]]
    if cfg.name.startswith("gemma2"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def logits_from_hidden(cfg: ArchConfig, params: Params, x: jax.Array):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    return L.softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)


def forward(params: Params, cfg: ArchConfig, batch: Dict[str, jax.Array], *,
            attn_impl: str = "flash", collect_cache: bool = False):
    """Full-sequence forward. Returns (hidden [B,S,d], moe_aux_loss) — plus the
    decode cache (KV stacks / SSM states) when ``collect_cache`` (prefill)."""
    x = embed_tokens(cfg, params, batch)
    x = constrain(x, "batch", None, None)  # pin batch->data in the residual
    bsz, s, d = x.shape
    positions = jnp.arange(s)
    aux0 = jnp.zeros((), jnp.float32)
    cache = None

    if cfg.family == "ssm":
        seq_ax = "model" if cfg.seq_shard_activations else None

        def body(carry, lp):
            h = constrain(_barrier(carry), "batch", seq_ax, None)
            y, st = SSM.mamba1_apply(lp["mamba"], L.rms_norm(h, lp["norm"]),
                                     cfg.ssm, chunk=cfg.ssm.chunk,
                                     return_state=True)
            return h + y, st
        x, states = jax.lax.scan(_remat(body, cfg.remat_policy), x,
                                 params["layers"])
        if collect_cache:
            cache = {"conv": states["conv"], "ssm": states["ssm"]}
        aux = aux0
    elif cfg.family == "hybrid":
        shared = params["shared"]

        seq_ax = "model" if cfg.seq_shard_activations else None

        def group_body(carry, gp):
            h = constrain(_barrier(carry), "batch", seq_ax, None)

            def mamba_body(hh, mp):
                y, st = SSM.mamba2_apply(mp["mamba"],
                                         L.rms_norm(hh, mp["norm_m"]),
                                         cfg.ssm, return_state=True)
                return hh + y, st
            h, mstates = jax.lax.scan(
                mamba_body, h,
                {"mamba": gp["mamba"], "norm_m": gp["norm_m"]})
            a, (k, v) = attn_block(shared["attn"],
                                   L.rms_norm(h, gp["norm_attn"]), cfg,
                                   positions=positions,
                                   window=cfg.sliding_window,
                                   attn_impl=attn_impl, return_kv=True)
            h = h + a
            m = L.mlp_apply(shared["mlp"], L.rms_norm(h, gp["norm_mlp"]),
                            cfg.mlp_act)
            return h + m, (mstates, k, v)
        x, (mstates, ks, vs) = jax.lax.scan(
            _remat(group_body, cfg.remat_policy), x, params["groups"])
        if collect_cache:
            cache = {"m_conv": mstates["conv"], "m_ssm": mstates["ssm"],
                     "k": ks, "v": vs}
        aux = aux0
    else:
        nl = cfg.n_layers
        layer_idx = jnp.arange(nl)

        seq_ax = "model" if cfg.seq_shard_activations else None

        def body(carry, xs):
            h, aux = carry
            h = constrain(_barrier(h), "batch", seq_ax, None)
            lp, idx = xs
            window = _layer_window(cfg, idx)
            a, (k, v) = attn_block(lp["attn"], L.rms_norm(h, lp["norm1"]), cfg,
                                   positions=positions, window=window,
                                   attn_impl=attn_impl, return_kv=True)
            h = h + a
            hn = L.rms_norm(h, lp["norm2"])
            if cfg.moe is not None:
                m, aux_l = MOE.moe_apply(lp["moe"], hn, cfg.moe, cfg.mlp_act)
                aux = aux + aux_l
            else:
                m = L.mlp_apply(lp["mlp"], hn, cfg.mlp_act)
            # barrier on the OUTPUT carry as well: without it XLA saves the
            # next iteration's rms_norm f32 upcast of this carry instead of
            # the bf16 value (a 2x f32 stacked-residual buffer — observed
            # 7.9 GB/device at llama3 train_4k)
            return (_barrier(h + m), aux), (k, v) if collect_cache else None
        (x, aux), kv = jax.lax.scan(_remat(body, cfg.remat_policy), (x, aux0),
                                    (params["layers"], layer_idx))
        if collect_cache:
            cache = {"k": kv[0], "v": kv[1]}

    x = L.rms_norm(x, params["final_norm"])
    if collect_cache:
        return x, aux, cache
    return x, aux


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def chunked_softmax_xent(cfg: ArchConfig, params: Params, hidden: jax.Array,
                         labels: jax.Array, chunk: int = 512) -> jax.Array:
    """Next-token CE without materialising [B, S, V] logits (scan over S-chunks).

    For 128k–256k vocabs at 1M tokens the full logits tensor is the single
    largest allocation in the step; chunking removes it (beyond-paper memory
    optimization, see EXPERIMENTS.md §Perf).
    """
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    assert s % chunk == 0
    hs = jnp.moveaxis(hidden.reshape(b, s // chunk, chunk, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, s // chunk, chunk), 1, 0)

    def body(tot, xs):
        h, y = xs
        logits = logits_from_hidden(cfg, params, h)  # [B, chunk, V] f32
        logits = constrain(logits, "batch", None, "model")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    # checkpoint: recompute each chunk's logits in backward instead of saving
    # [B, chunk, V] f32 per chunk (8 x 524 MB/device at 256k vocab)
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ls))
    return tot / (b * s)


def loss_fn(params: Params, cfg: ArchConfig, batch: Dict[str, jax.Array], *,
            attn_impl: str = "flash", aux_weight: float = 0.01) -> jax.Array:
    hidden, aux = forward(params, cfg, batch, attn_impl=attn_impl)
    ce = chunked_softmax_xent(cfg, params, hidden, batch["labels"])
    return ce + aux_weight * aux

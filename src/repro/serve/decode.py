"""Serving steps: prefill (context ingestion -> logits + cache) and one-token
decode. These are the "GPU task" bodies for inference workloads.

Ring-cache note: pure-SWA archs (mixtral) keep an O(window) ring buffer; after
a prefill of S tokens the last ``window`` K/V rows are rotated into ring order
(slot = position % window) so decode can continue writing at ``pos % window``.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import decode as D
from repro.models.model import forward, logits_from_hidden


def make_prefill_step(cfg: ArchConfig, *, attn_impl: str = "flash"):
    """prefill(params, batch) -> (last-token logits [B, V], cache).

    Sequence-sharded activations are DISABLED for prefill: inference saves
    nothing for a backward pass, so SP buys no memory here and its per-layer
    gathers only add collective traffic (qwen prefill_32k: 87 GB/device with
    SP vs 13 GB without).
    """
    import dataclasses
    if cfg.seq_shard_activations:
        cfg = dataclasses.replace(cfg, seq_shard_activations=False)

    def prefill(params, batch):
        hidden, _, cache = forward(params, cfg, batch, attn_impl=attn_impl,
                                   collect_cache=True)
        logits = logits_from_hidden(cfg, params, hidden[:, -1:])[:, 0]
        if D.uses_ring(cfg) and "k" in cache:
            w = cfg.sliding_window
            s = hidden.shape[1]
            if s >= w:
                # the last w positions land at ring slots (s-w+i) % w;
                # rolling the tail by s % w puts position p at slot p % w,
                # exactly where decode_step resumes writing (verified
                # slot-by-slot against a pure-decode ring in tests)
                tail = jax.tree_util.tree_map(
                    lambda t: jnp.roll(t[:, :, :, -w:], s % w, axis=3),
                    {"k": cache["k"], "v": cache["v"]})
                cache = tail
            else:
                # ring not yet full: slots 0..s-1 already hold positions
                # 0..s-1 (p % w == p for p < w) — but the ring MODULUS that
                # decode_step uses is the cache's seq dim, so handing back an
                # s-deep cache would wrap the ring at s instead of w. Pad to
                # the full ring size; the empty slots are masked (cache_len)
                # until decode writes them.
                cache = jax.tree_util.tree_map(
                    lambda t: jnp.pad(t, [(0, 0)] * 3 + [(0, w - s)]
                                      + [(0, 0)] * (t.ndim - 4)),
                    {"k": cache["k"], "v": cache["v"]})
        if cfg.kv_cache_dtype == "int8" and "k" in cache \
                and cfg.family != "hybrid":
            from repro.models.layers import quantize_kv
            kq, ks = quantize_kv(cache["k"])
            vq, vs = quantize_kv(cache["v"])
            cache = {"k": kq, "v": vq, "k_s": ks, "v_s": vs}
        return logits, cache

    return prefill


def make_serve_step(cfg: ArchConfig):
    """serve_step(params, cache, tokens [B], pos) -> (logits [B,V], cache).

    One new token against a KV/SSM cache — the ``decode_*``/``long_*`` shapes
    lower THIS function, not train_step.
    """

    def serve_step(params, cache, tokens, pos):
        return D.decode_step(params, cfg, cache, tokens, pos)

    return serve_step


def greedy_generate(cfg: ArchConfig, params, cache, first_tokens, start_pos,
                    num_steps: int):
    """Greedy generation loop (lax.scan over steps) for the examples.

    ``num_steps=0`` (a gen_len-1 request) is a valid degenerate call and
    returns an empty [B, 0] token block with the cache untouched.
    """
    if num_steps <= 0:
        b = first_tokens.shape[0]
        return jnp.zeros((b, 0), jnp.int32), cache
    serve = make_serve_step(cfg)

    def body(carry, _):
        tokens, pos, cache = carry
        logits, new = serve(params, cache, tokens, pos)
        # the scan carry keeps the cache's dtypes (f32 weights would
        # otherwise promote a bf16 recurrent state on the first step)
        cache = jax.tree_util.tree_map(lambda n, o: n.astype(o.dtype),
                                       new, cache)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (nxt, pos + 1, cache), nxt

    (_, _, cache), toks = jax.lax.scan(
        body, (first_tokens, jnp.asarray(start_pos, jnp.int32), cache),
        None, length=num_steps)
    return jnp.moveaxis(toks, 0, 1), cache  # [B, num_steps]


def abstract_cache(cfg: ArchConfig, batch: int, max_seq: int,
                   dtype=jnp.bfloat16):
    return jax.eval_shape(
        functools.partial(D.init_cache, cfg, batch, max_seq, dtype))

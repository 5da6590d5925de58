"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` resolves through ``_default_interpret``: compiled by
Mosaic on a TPU, run by the Pallas interpreter on any other backend (the
CPU test suite, which checks them against the ref.py oracles). The kernel
modules themselves compile by default; interpret mode is only ever chosen
here or passed explicitly by a test.
"""
from __future__ import annotations

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import mamba_scan as _ms
from repro.kernels import moe_gmm as _gmm
from repro.kernels import rmsnorm as _rn


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal=True, window=0, logit_softcap=0.0,
                    q_offset=0, block_q=128, block_k=128, interpret=None):
    assert q_offset == 0, "pallas path is train/prefill only (q_offset=0)"
    window = int(window) if not hasattr(window, "aval") else window
    if hasattr(window, "aval"):
        raise ValueError("pallas flash attention needs a static window; "
                         "use attn_impl='flash' for traced windows (gemma2)")
    return _fa.flash_attention(
        q, k, v, causal=causal, window=int(window or 0),
        logit_softcap=float(logit_softcap),
        block_q=block_q, block_k=block_k,
        interpret=_default_interpret() if interpret is None else interpret)


def rmsnorm(x, scale, *, eps=1e-5, interpret=None):
    return _rn.rmsnorm(
        x, scale, eps=eps,
        interpret=_default_interpret() if interpret is None else interpret)


def mamba_scan(a, b, *, chunk=64, interpret=None):
    return _ms.mamba_scan(
        a, b, chunk=chunk,
        interpret=_default_interpret() if interpret is None else interpret)


def moe_gmm(x, w, group_sizes, *, interpret=None):
    return _gmm.moe_gmm(
        x, w, group_sizes,
        interpret=_default_interpret() if interpret is None else interpret)

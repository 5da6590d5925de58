"""Kernel micro-benchmarks.

Wall-clock on this CPU container is meaningless for TPU kernels, so each row
reports (a) the compiled cost-analysis roofline estimate for the TARGET (TPU
v5e constants) of the pure-jnp reference vs. the kernel's access pattern, and
(b) CPU wall time of the jnp reference vs the naive formulation — evidence of
the algorithmic win (e.g. flash vs naive attention memory traffic).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks import common as C
from repro.core.probe import vector_from_compiled
from repro.models import layers as L


def _roofline_row(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    vec = vector_from_compiled(compiled)
    return {"flops": vec.flops, "bytes": vec.bytes_accessed,
            "tpu_est_us": vec.est_seconds * 1e6,
            "intensity": vec.flops / max(vec.bytes_accessed, 1)}


def _time(fn, *args, iters=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run() -> dict:
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    rows = {}

    # attention: naive vs flash (jnp) — bytes ratio is the flash win
    b, h, s, d = 2, 8, 2048, 64
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)
    naive = _roofline_row(
        lambda *a: L.naive_attention(*a), q, k, v)
    flash = _roofline_row(
        lambda *a: L.flash_attention_jnp(*a, block_k=512), q, k, v)
    rows["attention_naive"] = naive
    rows["attention_flash"] = flash
    rows["attention_bytes_ratio"] = naive["bytes"] / flash["bytes"]

    # rmsnorm fused vs unfused traffic
    x = jax.random.normal(ks[0], (4096, 4096), jnp.float32)
    sc = jax.random.normal(ks[1], (4096,)) * 0.1
    rows["rmsnorm"] = _roofline_row(lambda a, b2: L.rms_norm(a, b2), x, sc)

    # mamba scan: associative-scan reference traffic
    a = jnp.exp(-jnp.abs(jax.random.normal(ks[0], (2, 1024, 512, 16))))
    bb = jax.random.normal(ks[1], (2, 1024, 512, 16))
    from repro.kernels.ref import mamba_scan_ref
    rows["mamba_scan_ref"] = _roofline_row(
        lambda aa, bbb: mamba_scan_ref(aa, bbb, jnp.zeros((2, 512, 16))),
        a, bb)

    # wall-clock sanity on CPU (small shapes)
    qs, kss, vs = q[:, :, :512], k[:, :, :512], v[:, :, :512]
    rows["cpu_us_naive_attn"] = _time(
        jax.jit(lambda *t: L.naive_attention(*t)), qs, kss, vs)
    rows["cpu_us_flash_attn"] = _time(
        jax.jit(lambda *t: L.flash_attention_jnp(*t)), qs, kss, vs)

    print("kernels_bench:")
    print(f"  attention bytes naive/flash: "
          f"{rows['attention_bytes_ratio']:.1f}x less HBM traffic (flash)")
    for name in ("attention_naive", "attention_flash", "rmsnorm",
                 "mamba_scan_ref"):
        r = rows[name]
        print(f"  {name:18s} flops={r['flops']:.3g} bytes={r['bytes']:.3g} "
              f"AI={r['intensity']:.1f} tpu_est={r['tpu_est_us']:.0f}us")
    print(f"  cpu wall: naive {rows['cpu_us_naive_attn']:.0f}us vs "
          f"flash {rows['cpu_us_flash_attn']:.0f}us")
    C.save_json("kernels_bench.json", rows)
    return rows


if __name__ == "__main__":
    run()

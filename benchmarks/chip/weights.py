"""Seeded weights, made on the device in one jitted call.

The layout is the program's parameter tree (nested dicts, layers stacked
on leading axes), written out here from a configuration file's sizes so
that the plain reference can regenerate exactly the same values from the
same seed without importing the program. ``run.py`` checks the layout
against the program's own ``init_params`` shapes before it hands the
weights over, so a change of layout fails loudly instead of silently.
"""
from __future__ import annotations

import functools
import math

import numpy as np


def jax_seed(seed: int, salt: int = 0) -> int:
    """A 31-bit JAX seed from any whole ``seed`` (the harness's seeds exceed
    32 signed bits), distinct per ``salt``."""
    return int(np.random.default_rng([int(seed) % (1 << 63), salt])
               .integers(0, 2**31 - 1))


def layout(cfg: dict) -> dict:
    """{path tuple: (shape, dtype name, init)} of every weight. ``init`` is
    ("normal", std) | ("const", value) | ("a_log", lo, hi) | ("dt_bias",)."""
    d, v = cfg["d_model"], cfg["vocab"]
    wd = cfg["dtype"]
    out = {("embed",): ((v, d), wd, ("normal", 0.02))}
    ssm = cfg.get("ssm")
    if cfg["family"] == "hybrid":
        k = cfg["hybrid_shared_every"]
        g = cfg["n_layers"] // k
        st = (g, k - 1)
        e, n = ssm["expand"] * d, ssm["state_dim"]
        nh, w = e // ssm["headdim"], ssm["conv_width"]
        m = ("groups", "mamba")
        out.update({
            m + ("in_proj",): (st + (d, 2 * e + 2 * n + nh), wd,
                               ("normal", d ** -0.5)),
            m + ("conv_w",): (st + (e + 2 * n, w), wd, ("normal", 0.2)),
            m + ("conv_b",): (st + (e + 2 * n,), wd, ("normal", 0.02)),
            m + ("dt_bias",): (st + (nh,), "float32", ("dt_bias",)),
            m + ("A_log",): (st + (nh,), "float32", ("a_log", 1.0, 16.0)),
            m + ("D",): (st + (nh,), "float32", ("const", 1.0)),
            m + ("norm",): (st + (e,), wd, ("normal", 0.05)),
            m + ("out_proj",): (st + (e, d), wd, ("normal", e ** -0.5)),
            ("groups", "norm_m"): (st + (d,), wd, ("normal", 0.05)),
            ("groups", "norm_attn"): ((g, d), wd, ("normal", 0.05)),
            ("groups", "norm_mlp"): ((g, d), wd, ("normal", 0.05)),
        })
        h, kv, hd, f = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"], \
            cfg["d_ff"]
        a = ("shared", "attn")
        out.update({
            a + ("wq",): ((d, h, hd), wd, ("normal", d ** -0.5)),
            a + ("wk",): ((d, kv, hd), wd, ("normal", d ** -0.5)),
            a + ("wv",): ((d, kv, hd), wd, ("normal", d ** -0.5)),
            a + ("wo",): ((h, hd, d), wd, ("normal", (h * hd) ** -0.5)),
            ("shared", "mlp", "wi"): ((d, f), wd, ("normal", d ** -0.5)),
            ("shared", "mlp", "wg"): ((d, f), wd, ("normal", d ** -0.5)),
            ("shared", "mlp", "wo"): ((f, d), wd, ("normal", f ** -0.5)),
        })
    else:
        raise ValueError(f"no weight layout for family {cfg['family']!r}")
    out[("final_norm",)] = ((d,), wd, ("normal", 0.05))
    out[("lm_head",)] = ((d, v), wd, ("normal", d ** -0.5))
    return out


def _leaf(key, shape, dtype, init):
    import jax
    import jax.numpy as jnp
    kind = init[0]
    if kind == "normal":
        x = jax.random.normal(key, shape, jnp.float32) * init[1]
    elif kind == "const":
        x = jnp.full(shape, init[1], jnp.float32)
    elif kind == "a_log":
        # Mamba-2: A per head drawn uniformly from [lo, hi], stored as log A
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                       init[1], init[2]))
    elif kind == "dt_bias":
        # the step size after softplus lies log-uniformly in [1e-3, 1e-1]
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        x = dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1(dt)
    else:
        raise ValueError(init)
    return x.astype(dtype)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf
    return out


@functools.lru_cache(maxsize=8)
def _maker(items: tuple):
    """The jitted generator for one layout (``items`` is hashable)."""
    import jax

    def make(key):
        keys = jax.random.split(key, len(items))
        return _nest({path: _leaf(k, shape, dtype, init)
                      for k, (path, shape, dtype, init) in zip(keys, items)})
    return jax.jit(make)


def _items(cfg: dict) -> tuple:
    return tuple((p, s, dt, i) for p, (s, dt, i) in sorted(layout(cfg).items()))


def make_params(cfg: dict, seed: int, device=None):
    """All weights of ``cfg`` from ``seed``, in one jitted call on
    ``device`` (default: the default device)."""
    import jax
    key = jax.random.PRNGKey(jax_seed(seed, 1))
    if device is not None:
        key = jax.device_put(key, device)
    return _maker(_items(cfg))(key)


def abstract_params(cfg: dict):
    """Shapes and dtypes of ``make_params`` without computing it."""
    import jax
    return jax.eval_shape(_maker(_items(cfg)), jax.random.PRNGKey(0))


def param_bytes(cfg: dict) -> int:
    import jax.numpy as jnp
    return sum(math.prod(s) * jnp.dtype(dt).itemsize
               for s, dt, _ in layout(cfg).values())

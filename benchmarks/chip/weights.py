"""Seeded weights, made on the device in one jitted call.

The layout is the program's parameter tree (nested dicts, layers stacked
on leading axes), written out from a configuration file's sizes by the
architecture's file (``archs/<program_arch>.py``), so that the plain
reference can regenerate exactly the same values from the same seed
without importing the program. ``harness.check_layout`` checks it
against the program's own ``init_params`` shapes before the weights are
handed over, so a change of layout fails loudly instead of silently.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import arch


def jax_seed(seed: int, salt: int = 0) -> int:
    """A 31-bit JAX seed from any whole ``seed`` (the harness's seeds exceed
    32 signed bits), distinct per ``salt``."""
    return int(np.random.default_rng([int(seed) % (1 << 63), salt])
               .integers(0, 2**31 - 1))


def layout(cfg: dict) -> dict:
    """{path tuple: (shape, dtype name, init)} of every weight, as the
    architecture's file writes it out. ``init`` is ("normal", std) |
    ("const", value) | ("a_log", lo, hi) | ("dt_bias",)."""
    return arch.module(cfg).layout(cfg)


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

"""Lazy runtime + compiler-guided probe tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lazy
from repro.core.probe import probe_fn


def test_lazy_buffer_records_without_allocation():
    buf = lazy.LazyBuffer("x").alloc((8, 8), jnp.float32)
    assert buf._real is None and buf.nbytes == 256
    buf.fill(3.0)
    assert buf._real is None  # still nothing on device


def test_lazy_replay_h2d():
    host = np.arange(16, dtype=np.float32).reshape(4, 4)
    buf = lazy.LazyBuffer("x").h2d(host)
    dev = jax.devices()[0]
    arr = buf.bind(dev)
    np.testing.assert_array_equal(np.asarray(arr), host)


def test_lazy_rebind_to_other_device_after_free():
    buf = lazy.LazyBuffer("x").fill(2.5).alloc((4,), jnp.float32)
    # alloc after fill resets shape; do it properly
    buf2 = lazy.LazyBuffer("y").alloc((4,), jnp.float32).fill(2.5)
    dev = jax.devices()[0]
    a = buf2.bind(dev)
    np.testing.assert_allclose(np.asarray(a), 2.5)
    buf2.free()
    assert buf2._real is None
    b = buf2.bind(dev)  # replay again — the paper's device reassignment
    np.testing.assert_allclose(np.asarray(b), 2.5)


def test_kernel_launch_prepare_binds_all():
    bufs = {"a": lazy.LazyBuffer("a").h2d(np.ones((2, 2), np.float32)),
            "b": lazy.LazyBuffer("b").alloc((2, 2), jnp.float32)}
    arrs = lazy.kernel_launch_prepare(bufs, jax.devices()[0])
    assert set(arrs) == {"a", "b"}
    np.testing.assert_allclose(np.asarray(arrs["b"]), 0.0)  # bare alloc=zeros


def test_probe_memory_matches_analytic():
    n = 256

    def f(x, y):
        return x @ y

    sds = jax.ShapeDtypeStruct((n, n), jnp.float32)
    vec = probe_fn(f, sds, sds)
    # 2 args + 1 output of n*n*4 bytes; temps small for a single matmul
    expect = 3 * n * n * 4
    assert expect <= vec.hbm_bytes <= expect * 1.5
    # flops ~= 2 n^3
    assert 0.5 <= vec.flops / (2 * n**3) <= 1.5
    assert 0 < vec.core_demand <= 1 and 0 < vec.bw_demand <= 1
    assert vec.est_seconds > 0


def test_probe_efficiency_scales_demand():
    def f(x):
        return jnp.sum(x * 2.0)  # memory-bound

    sds = jax.ShapeDtypeStruct((1 << 20,), jnp.float32)
    full = probe_fn(f, sds)
    half = probe_fn(f, sds, efficiency=(1.0, 0.5))
    assert half.est_seconds > full.est_seconds * 1.8
    assert half.bw_demand <= 0.55


def test_probe_work_scale():
    def f(x):
        return x + 1

    sds = jax.ShapeDtypeStruct((1024,), jnp.float32)
    v1 = probe_fn(f, sds, work_scale=1.0)
    v10 = probe_fn(f, sds, work_scale=10.0)
    assert abs(v10.est_seconds - 10 * v1.est_seconds) < 1e-9
    assert v10.hbm_bytes == v1.hbm_bytes  # footprint does not scale

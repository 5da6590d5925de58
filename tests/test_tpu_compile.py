"""Compile every Pallas kernel for a described TPU v5e at real model widths.

Nothing runs: the TPU compiler, which is installed beside the CPU backend,
compiles for a chip that is described and not attached. This catches what
interpret mode cannot — VMEM over-subscription, tile misalignment, a block
that does not fit — before any chip time is spent. The topology is described
inside a fixture (never at import), so only the test worker that runs this
file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as FA
from repro.kernels import mamba_scan as MS
from repro.kernels import moe_gmm as GMM
from repro.kernels import rmsnorm as RN


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without
    # one: keep the persistent cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch,b,hq,hkv,s,d,window,softcap", [
    # zamba2-2.7b shared attention: 32 heads of head_dim 80, 512-token prompt
    ("zamba2-2.7b", 1, 32, 32, 512, 80, 0, 0.0),
    # gemma2-9b local layers: 16 q / 8 kv heads of 256, 4096 window, softcap
    ("gemma2-9b", 1, 16, 8, 8192, 256, 4096, 50.0),
])
def test_flash_attention_compiles(one_chip, arch, b, hq, hkv, s, d, window,
                                  softcap):
    q = _sds((b, hq, s, d), jnp.bfloat16, one_chip)
    kv = _sds((b, hkv, s, d), jnp.bfloat16, one_chip)
    _assert_mosaic(lambda q, k, v: FA.flash_attention(
        q, k, v, causal=True, window=window, logit_softcap=softcap),
        q, kv, kv)


def test_rmsnorm_compiles(one_chip):
    # zamba2-2.7b residual stream: d_model 2560, 4096 rows
    x = _sds((4096, 2560), jnp.bfloat16, one_chip)
    sc = _sds((2560,), jnp.bfloat16, one_chip)
    _assert_mosaic(lambda x, sc: RN.rmsnorm(x, sc), x, sc)


def test_mamba_scan_compiles(one_chip):
    # falcon-mamba-7b: d_inner 8192 (expand 2 x d_model 4096), state 16
    a = _sds((1, 1024, 8192, 16), jnp.float32, one_chip)
    _assert_mosaic(lambda a, b: MS.mamba_scan(a, b), a, a)


def test_moe_gmm_compiles(one_chip):
    # mixtral-8x7b expert FFN: 8 experts, d_model 4096 -> d_ff 14336
    x = _sds((4096, 4096), jnp.bfloat16, one_chip)
    w = _sds((8, 4096, 14336), jnp.bfloat16, one_chip)
    gs = _sds((8,), jnp.int32, one_chip)
    _assert_mosaic(lambda x, w, gs: GMM.moe_gmm(x, w, gs), x, w, gs)

"""The persistent compilation cache's location rule
(``repro.launch.compile_cache``): ``JAX_COMPILATION_CACHE_DIR`` when set,
and nothing else is set; otherwise one fixed directory in the checkout."""
import os

import jax
import pytest

from repro.launch import compile_cache as CC

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("env_dir", ["/cache/from/env", None])
def test_cache_dir_rule(monkeypatch, cache_config, env_dir):
    if env_dir:
        monkeypatch.setenv(CC.CACHE_ENV, env_dir)
    else:
        monkeypatch.delenv(CC.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    got = CC.enable_compile_cache()
    if env_dir:
        # JAX reads the variable itself: the config is left alone
        assert got == env_dir
        assert jax.config.jax_compilation_cache_dir == before
    else:
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    # the same directory on every call: a later run must find it again
    assert CC.enable_compile_cache() == got

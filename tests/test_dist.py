"""Distribution-layer tests that need >1 host device: run in a subprocess
with XLA_FLAGS so the main pytest process keeps seeing 1 device (per the
dry-run isolation rule)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_in_subprocess(code: str) -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=500)
    assert proc.returncode == 0, proc.stderr[-3000:]


def test_pipeline_parallel_matches_sequential():
    _run_in_subprocess("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.dist.pipeline import make_pipeline_forward, \\
            stack_stage_params
        mesh = make_mesh((4,), ("stage",))
        L, d = 8, 32
        w = jax.random.normal(jax.random.PRNGKey(0), (L, d, d)) * 0.1
        def layer_fn(sp, x):
            h, _ = jax.lax.scan(lambda h, wl: (jnp.tanh(h @ wl), None), x, sp)
            return h
        for n_micro in (4, 8):
            pipe = make_pipeline_forward(layer_fn, mesh, n_micro=n_micro)
            x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, d))
            y = pipe(stack_stage_params(w, 4), x)
            ref, _ = jax.lax.scan(
                lambda h, wl: (jnp.tanh(h @ wl), None), x, w)
            assert jnp.abs(y - ref).max() < 1e-5, n_micro
    """)


def test_elastic_reshard_preserves_state():
    _run_in_subprocess("""
        import jax, jax.numpy as jnp
        from repro.configs.registry import get_arch
        from repro.models.model import init_params
        from repro.optim import adamw
        from repro.train.elastic import reshard_state, rescale_batch_size
        from repro.launch.mesh import make_mesh
        cfg = get_arch("llama3-405b").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw.init_state(adamw.AdamWConfig(), params)
        mesh_big = make_mesh((4, 2), ("data", "model"))
        mesh_small = make_mesh((2, 2), ("data", "model"))
        p1, o1 = reshard_state(cfg, params, opt, mesh_big)
        p2, o2 = reshard_state(cfg, p1, o1, mesh_small)   # shrink 8 -> 4
        ok = jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda a, b: bool(jnp.allclose(a, b)), params, p2))
        assert ok
        assert rescale_batch_size(256, 16, 8) == 128
    """)


def test_sharded_train_step_matches_single_device():
    """The same train step on a 4-device mesh must produce the same loss
    trajectory as unsharded execution (SPMD correctness)."""
    _run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import ShapeConfig
        from repro.configs.registry import get_arch
        from repro.dist import sharding as SH
        from repro.launch.mesh import make_mesh
        from repro.models.model import init_params
        from repro.optim import adamw
        from repro.train.train_step import make_train_step
        cfg = get_arch("qwen1.5-32b").reduced()
        opt_cfg = adamw.AdamWConfig()
        step = make_train_step(cfg, opt_cfg, attn_impl="flash_jnp")
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw.init_state(opt_cfg, params)
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab)
        batch = {"tokens": tok, "labels": jnp.roll(tok, -1, axis=1)}
        # unsharded reference
        p1, o1, m1 = jax.jit(step)(params, opt, batch)
        # sharded on (data=4, model=2)
        mesh = make_mesh((4, 2), ("data", "model"))
        with SH.activation_mesh(mesh):
            psh = SH.to_named(SH.param_specs(cfg, params, mesh), mesh)
            bsh = SH.to_named(SH.batch_specs(cfg, batch, mesh), mesh)
            params_s = jax.tree_util.tree_map(jax.device_put, params, psh)
            opt_s = {
                "mu": jax.tree_util.tree_map(
                    jax.device_put, opt["mu"], psh),
                "nu": jax.tree_util.tree_map(
                    jax.device_put, opt["nu"], psh),
                "step": jax.device_put(opt["step"],
                                       NamedSharding(mesh, P())),
            }
            batch_s = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
            p2, o2, m2 = jax.jit(step)(params_s, opt_s, batch_s)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
        diffs = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                       - b.astype(jnp.float32)).max()),
            p1, p2)
        assert max(jax.tree_util.tree_leaves(diffs)) < 1e-2
    """)


def test_gradient_compression_in_train_step():
    _run_in_subprocess("""
        import jax, jax.numpy as jnp
        from repro.configs.registry import get_arch
        from repro.dist import compression as C
        from repro.models.model import init_params
        from repro.optim import adamw
        from repro.train.train_step import make_train_step
        cfg = get_arch("qwen1.5-32b").reduced()
        opt_cfg = adamw.AdamWConfig()
        step = make_train_step(cfg, opt_cfg, attn_impl="flash_jnp",
                               grad_compressor=lambda g: jax.tree_util.
                               tree_map(C.compress_decompress, g))
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw.init_state(opt_cfg, params)
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab)
        batch = {"tokens": tok, "labels": jnp.roll(tok, -1, axis=1)}
        losses = []
        jstep = jax.jit(step)
        for _ in range(4):
            params, opt, m = jstep(params, opt, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]   # still optimizes under compression
    """)

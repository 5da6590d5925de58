"""End-to-end training driver: data pipeline -> sharded train loop with
checkpointing, straggler detection, and (optionally) the compiler-guided
scheduler wrapping the whole run as a GPU task.

Scales from this CPU container (reduced config, 1x1 mesh) to a production
pod (full config, 16x16 mesh) with no code change — only --mesh/--reduced.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch gemma2-9b --reduced \
        --steps 50 --batch 8 --seq 128 [--ckpt-dir /tmp/ck] [--resume]
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ShapeConfig
from repro.configs.registry import ARCHS, get_arch
from repro.data.pipeline import Prefetcher, TokenPipeline, shard_batch
from repro.dist import sharding as SH
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import data_axes, make_mesh
from repro.models.model import init_params
from repro.optim import adamw
from repro.train import checkpoint as CK
from repro.train.straggler import StragglerDetector
from repro.train.train_step import make_train_step


def train(arch: str, *, steps: int = 50, batch: int = 8, seq: int = 128,
          reduced: bool = True, mesh_shape=(1, 1), ckpt_dir: Optional[str] = None,
          ckpt_every: int = 20, resume: bool = False, seed: int = 0,
          attn_impl: str = "flash", log_every: int = 10,
          lr: float = 3e-4) -> dict:
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    mesh = make_mesh(mesh_shape, ("data", "model"))
    shape = ShapeConfig("driver", seq, batch, "train")
    opt_cfg = adamw.AdamWConfig(lr=lr, warmup_steps=min(20, steps // 5 + 1),
                                total_steps=steps,
                                moment_dtype=cfg.optimizer_moment_dtype)
    step_fn = make_train_step(cfg, opt_cfg, attn_impl=attn_impl)

    with SH.activation_mesh(mesh):
        params = init_params(cfg, jax.random.PRNGKey(seed))
        opt_state = adamw.init_state(opt_cfg, params)
        pspecs = SH.param_specs(cfg, params, mesh)
        psh = SH.to_named(pspecs, mesh)
        osh = {"mu": psh, "nu": psh, "step": NamedSharding(mesh, P())}
        params = jax.tree_util.tree_map(jax.device_put, params, psh)
        opt_state = {
            "mu": jax.tree_util.tree_map(jax.device_put, opt_state["mu"], psh),
            "nu": jax.tree_util.tree_map(jax.device_put, opt_state["nu"], psh),
            "step": jax.device_put(opt_state["step"], osh["step"]),
        }

        start_step = 0
        ckpt = CK.AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
        if resume and ckpt_dir and CK.latest_step(ckpt_dir) is not None:
            start_step, state = CK.restore(
                ckpt_dir, {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            params = jax.tree_util.tree_map(jax.device_put, params, psh)
            print(f"[train] resumed from step {start_step}")

        pipe = TokenPipeline(cfg, shape, seed=seed, start_step=start_step,
                             batch_override=batch, seq_override=seq)
        prefetch = Prefetcher(pipe)
        bsh = SH.to_named(SH.batch_specs(
            cfg, jax.eval_shape(lambda: pipe.batch_at(0)), mesh), mesh)

        jstep = jax.jit(step_fn, donate_argnums=(0, 1))
        det = StragglerDetector(n_hosts=1)
        losses = []
        t_start = time.time()
        for step in range(start_step, steps):
            b = shard_batch(next(prefetch), bsh)
            t0 = time.time()
            params, opt_state, metrics = jstep(params, opt_state, b)
            loss = float(metrics["loss"])
            det.record_step(0, time.time() - t0)
            losses.append(loss)
            if step % log_every == 0 or step == steps - 1:
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e}", flush=True)
            if ckpt and (step + 1) % ckpt_every == 0:
                ckpt.save(step + 1, {"params": params, "opt": opt_state})
        if ckpt:
            ckpt.save(steps, {"params": params, "opt": opt_state})
            ckpt.wait()
        prefetch.close()
        wall = time.time() - t_start
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "wall_s": wall, "steps": steps - start_step,
            "stragglers": det.stragglers()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b", choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="full config (needs a pod); default is reduced")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--attn-impl", default="flash",
                    choices=["flash", "flash_jnp", "naive", "pallas"])
    args = ap.parse_args()
    enable_compile_cache()
    res = train(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
                reduced=not args.full, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every, resume=args.resume,
                attn_impl=args.attn_impl)
    print(f"[train] done: final_loss={res['final_loss']:.4f} "
          f"wall={res['wall_s']:.1f}s "
          f"({res['steps'] / res['wall_s']:.2f} steps/s)")


if __name__ == "__main__":
    main()

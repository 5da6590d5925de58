"""Scenario: a shared multi-accelerator node running a mixed batch of REAL
model workloads (train steps, prefill, decode) from independent "users" under
the paper's scheduler — the full compiler-guided pipeline with live JAX
execution through the event-driven executor (blocked jobs hold no thread;
completions wake the waiter queue), plus a mid-run device failure to exercise
the fault-tolerance path and a decode fleet far larger than the execution
pool.

    PYTHONPATH=src python examples/shared_cluster.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig
from repro.configs.registry import get_arch
from repro.core.cluster import Cluster, JobStatus
from repro.core.executor import ExecJob, Executor
from repro.core.probe import probe_fn
from repro.core.scheduler import MGBAlg3Scheduler, SAScheduler
from repro.core.task import Job, Task, UnitTask
from repro.models.model import init_params
from repro.optim import adamw
from repro.serve.decode import make_prefill_step
from repro.train.train_step import make_train_step

BATCH, SEQ = 4, 128


def make_train_job(arch: str, idx: int, steps: int = 3) -> ExecJob:
    cfg = get_arch(arch).reduced()
    opt_cfg = adamw.AdamWConfig()
    step = make_train_step(cfg, opt_cfg, attn_impl="flash_jnp")
    params = init_params(cfg, jax.random.PRNGKey(idx))
    opt_state = adamw.init_state(opt_cfg, params)
    rng = np.random.default_rng(idx)
    tok = jnp.asarray(rng.integers(0, cfg.vocab, (BATCH, SEQ), np.int32))
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, axis=1)}
    if cfg.embedding_frontend_stub:
        batch["embeds"] = jnp.asarray(
            rng.standard_normal((BATCH, SEQ, cfg.d_model), np.float32))
    vec = probe_fn(step, params, opt_state, batch, work_scale=steps)
    name = f"train-{arch}-{idx}"

    state = {"params": params, "opt": opt_state}

    def runner(device):
        # the job computes on the device it was placed on
        jstep = jax.jit(step)
        state["params"], state["opt"] = jax.device_put(
            (state["params"], state["opt"]), device)
        on_dev = jax.device_put(batch, device)
        for _ in range(steps):
            state["params"], state["opt"], m = jstep(
                state["params"], state["opt"], on_dev)
        jax.block_until_ready(m["loss"])

    unit = UnitTask(fn=None, memobjs=frozenset({name}), resources=vec,
                    name=name)
    return ExecJob(job=Job(tasks=[Task(units=[unit], name=name)], name=name),
                   runners=[runner])


def make_serve_job(arch: str, idx: int) -> ExecJob:
    cfg = get_arch(arch).reduced()
    prefill = make_prefill_step(cfg, attn_impl="flash_jnp")
    params = init_params(cfg, jax.random.PRNGKey(100 + idx))
    rng = np.random.default_rng(100 + idx)
    tok = jnp.asarray(rng.integers(0, cfg.vocab, (BATCH, SEQ), np.int32))
    batch = {"tokens": tok}
    if cfg.embedding_frontend_stub:
        batch["embeds"] = jnp.asarray(
            rng.standard_normal((BATCH, SEQ, cfg.d_model), np.float32))
    vec = probe_fn(prefill, params, batch)
    name = f"serve-{arch}-{idx}"

    def runner(device):
        logits, cache = jax.jit(prefill)(
            *jax.device_put((params, batch), device))
        jax.block_until_ready(logits)

    unit = UnitTask(fn=None, memobjs=frozenset({name}), resources=vec,
                    name=name)
    return ExecJob(job=Job(tasks=[Task(units=[unit], name=name)], name=name),
                   runners=[runner])


def build_jobs():
    jobs = []
    for i, arch in enumerate(["gemma2-9b", "qwen1.5-32b"]):
        jobs.append(make_train_job(arch, i))
    for i, arch in enumerate(["mixtral-8x7b", "falcon-mamba-7b",
                              "zamba2-2.7b", "musicgen-large"]):
        jobs.append(make_serve_job(arch, i))
    return jobs


def main():
    print("building 6 jobs (2 train + 4 serve) from 6 architectures...")
    jobs = build_jobs()
    for j in jobs:
        r = j.job.tasks[0].resources
        print(f"  {j.job.name:24s} mem={r.hbm_bytes / 1e6:7.1f} MB "
              f"demand={r.demand:.2f} est={r.est_seconds * 1e3:.2f} ms(tpu)")

    print("\n-- MGB Alg.3 on 2 virtual devices --")
    sched = MGBAlg3Scheduler(num_devices=2)
    t0 = time.time()
    stats = Executor(sched, workers=4).run(jobs)
    print(f"completed={stats['completed']} crashed={stats['crashed']} "
          f"makespan={stats['makespan_s']:.2f}s")
    by_dev = {}
    for uid, dev in sched.placements:
        by_dev.setdefault(dev, 0)
        by_dev[dev] += 1
    print("tasks per device:", by_dev)

    print("\n-- same jobs, SA baseline (one job per device) --")
    jobs2 = build_jobs()
    stats_sa = Executor(SAScheduler(num_devices=2), workers=2).run(jobs2)
    print(f"completed={stats_sa['completed']} "
          f"makespan={stats_sa['makespan_s']:.2f}s "
          f"(MGB speedup {stats_sa['makespan_s'] / stats['makespan_s']:.2f}x "
          f"on live CPU execution)")

    print("\n-- fault tolerance: kill device 0 mid-run --")
    sched3 = MGBAlg3Scheduler(num_devices=2)
    jobs3 = build_jobs()
    ex3 = Executor(sched3, workers=4)
    import threading

    def killer():
        time.sleep(0.3)
        evicted = sched3.mark_dead(0)
        print(f"  [failure injected] device 0 dead, {len(evicted)} task(s) "
              "evicted; survivors reschedule on device 1")
    threading.Thread(target=killer).start()
    stats3 = ex3.run(jobs3)
    print(f"completed={stats3['completed']} crashed={stats3['crashed']} "
          f"(all work landed on the surviving device)")
    assert stats3["completed"] + stats3["crashed"] == len(jobs3)

    print("\n-- decode fleet: 64 streamed decode requests, pool of 2, "
          "open arrival --")
    # the serving-scale path: every request is a task submitted to the live
    # Cluster AS IT ARRIVES — no pre-declared batch. Blocked requests park
    # in the scheduler's admission queue (no thread each) and completions
    # wake the next admission. Decode traffic is submitted at priority 5 so
    # it outranks the background training job streamed alongside it, and
    # each request carries a deadline (EDF within the priority class). One
    # jitted prefill is shared by the whole fleet.
    cfg = get_arch("zamba2-2.7b").reduced()
    prefill = jax.jit(make_prefill_step(cfg, attn_impl="flash_jnp"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, cfg.vocab, (2, 32), np.int32))
    fleet_batch = {"tokens": tok}
    if cfg.embedding_frontend_stub:
        fleet_batch["embeds"] = jnp.asarray(
            rng.standard_normal((2, 32, cfg.d_model), np.float32))
    vec = probe_fn(prefill, params, fleet_batch)

    def decode_runner(device):
        logits, _ = prefill(*jax.device_put((params, fleet_batch), device))
        jax.block_until_ready(logits)

    t0 = time.time()
    with Cluster(MGBAlg3Scheduler(num_devices=2), workers=2) as cluster:
        background = cluster.submit(make_train_job("gemma2-9b", 7),
                                    priority=0)
        handles = []
        for i in range(64):
            name = f"decode-{i}"
            unit = UnitTask(fn=None, memobjs=frozenset({name}),
                            resources=vec, name=name)
            handles.append(cluster.submit(
                ExecJob(job=Job(tasks=[Task(units=[unit], name=name)],
                                name=name),
                        runners=[decode_runner]),
                priority=5, deadline_s=30.0))
        first = handles[0].result(timeout=60)   # a single request's future
        cluster.drain()
        stats4 = cluster.stats()
    done = sum(1 for h in handles if h.status is JobStatus.DONE)
    print(f"completed={done}/64 decode + background train "
          f"{background.status.value} in {time.time() - t0:.2f}s "
          f"with 2 pool threads ({stats4['sched_attempts']} admission "
          f"attempts; first request {len(first)} record(s))")
    assert done == 64 and stats4["completed"] == 65
    print("\nshared_cluster OK")


if __name__ == "__main__":
    main()

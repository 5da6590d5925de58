"""End-to-end serving driver: prefill + decode under the compiler-guided
scheduler, with two serving disciplines over the same open-arrival
``Cluster`` front-end:

* **static** (default): every request batch is ONE GPU task whose resource
  vector comes from the compiled prefill/decode executables
  (repro.core.probe). Each batch is ``cluster.submit``-ed with a
  per-request deadline (EDF admission within its priority class), blocked
  batches hold no thread (they park in the scheduler's admission queue),
  and completions wake the next admission. Rows in the last batch beyond
  ``requests`` are shape padding — computed, but never counted as served
  tokens.
* **continuous** (``--continuous``): requests stream individually through
  ``repro.serve.engine.ServeEngine`` — per-device decode loops whose batch
  composition changes between steps; prefills are short high-priority
  tasks, each decode-slot join is a probed KV-delta admitted through the
  scheduler (memory-safe batch growth).

Both report per-request TTFT (arrival → first token) and TPOT (mean
inter-token time over the decode tail). The scheduler is built from the
attached devices: one scheduler device per chip, each with the chip's
``bytes_limit`` of HBM (``--num-devices`` more than are attached is refused
on an accelerator; on the CPU backend extra devices are virtual). The
reduced config runs by default; ``--full`` serves the published one with
bf16 weights.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
        --requests 16 --batch 4 --prompt-len 64 --gen-len 32 --deadline-s 5
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCHS, get_arch
from repro.core.cluster import Cluster, JobStatus
from repro.core.executor import ExecJob, device_capacity
from repro.core.probe import probe_fn
from repro.core.scheduler import MGBAlg3Scheduler, PreemptiveAlg3Scheduler
from repro.core.task import Job, Task, UnitTask
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import init_params
from repro.serve.decode import greedy_generate, make_prefill_step


def _pct(xs, p):
    if not xs:
        return 0.0
    xs = sorted(xs)
    i = min(int(p * (len(xs) - 1) + 0.5), len(xs) - 1)
    return xs[i]


def _model(arch: str, full: bool, seed: int):
    """(config, weights from ``seed``): the published config in bf16 when
    ``full``, else the reduced CPU-sized variant in f32."""
    cfg = get_arch(arch)
    if not full:
        return cfg.reduced(), init_params(cfg.reduced(),
                                          jax.random.PRNGKey(seed))
    return cfg, init_params(cfg, jax.random.PRNGKey(seed),
                            param_dtype=jnp.bfloat16)


def _fleet(num_devices):
    """(scheduler device count, per-device HBM) from the attached devices;
    ``num_devices`` overrides the count (virtual devices on the CPU)."""
    n, hbm = device_capacity()
    return (num_devices or n), hbm


def serve(arch: str, *, requests: int = 16, batch: int = 4,
          prompt_len: int = 64, gen_len: int = 32, seed: int = 0,
          num_devices: int = 0, workers: int = 0,
          deadline_s: float = 5.0, shed_late: bool = False,
          preempt: bool = False, full: bool = False,
          trace_path: str = None) -> dict:
    cfg, params = _model(arch, full, seed)
    prefill = jax.jit(make_prefill_step(cfg, attn_impl="flash_jnp"))
    num_devices, hbm = _fleet(num_devices)
    # preempt turns the deadline into the ENFORCEMENT half shedding cannot
    # give: an arriving earlier-deadline request may evict a resident one
    # (same priority class, EDF outranking) instead of waiting behind it
    sched = (PreemptiveAlg3Scheduler(num_devices, hbm_per_device=hbm)
             if preempt else MGBAlg3Scheduler(num_devices,
                                              hbm_per_device=hbm))

    rng = np.random.default_rng(seed)
    n_batches = (requests + batch - 1) // batch
    # real (non-padding) rows per batch: the final batch is shape-padded to
    # ``batch`` so every batch shares one compiled executable, but only
    # ``requests`` rows exist — padded rows must not count as served tokens
    rows = [min(batch, requests - i * batch) for i in range(n_batches)]
    # probe ONE representative batch (all batches share shapes, so they share
    # the compiled executable and the resource vector)
    first_prompts = jnp.asarray(rng.integers(
        0, cfg.vocab, (batch, prompt_len), dtype=np.int32))
    probe_batch = {"tokens": first_prompts}
    if cfg.embedding_frontend_stub:
        probe_batch["embeds"] = jnp.asarray(rng.standard_normal(
            (batch, prompt_len, cfg.d_model), dtype=np.float32))
    vec = probe_fn(prefill, params, probe_batch)

    # shed_late turns the deadline from an EDF ordering hint into (soft)
    # enforcement: a request still parked when its deadline passes is failed
    # with JobStatus.SHED at the next drain instead of served late
    cluster = Cluster(sched, workers=workers or num_devices,
                      shed_late=shed_late, preempt=preempt or None,
                      trace=bool(trace_path))
    handles = []
    # one weight replica on each device a batch can be placed on
    replicas = {d: jax.device_put(params, d) for d in
                {cluster.jax_device(k) for k in range(num_devices)}}
    # per-batch wall-clock marks filled by the runner: (submit, first-token,
    # last-token) — the per-request TTFT/TPOT instrumentation
    marks = [[0.0, -1.0, -1.0] for _ in range(n_batches)]
    t0 = time.time()
    # open arrival: each request batch is submitted as it "comes in", with
    # its own deadline — admission is EDF within the priority class, so
    # earlier-deadline requests claim freed capacity first
    for i in range(n_batches):
        b = dict(probe_batch) if i == 0 else {
            "tokens": jnp.asarray(rng.integers(
                0, cfg.vocab, (batch, prompt_len), dtype=np.int32))}
        if cfg.embedding_frontend_stub and "embeds" not in b:
            b["embeds"] = jnp.asarray(rng.standard_normal(
                (batch, prompt_len, cfg.d_model), dtype=np.float32))

        def runner(device, b=b, i=i):
            # a placed batch runs on its device, against that replica
            p = replicas[device]
            logits, cache = prefill(p, jax.device_put(b, device))
            first = jax.block_until_ready(
                jnp.argmax(logits, axis=-1).astype(jnp.int32))
            marks[i][1] = time.time()
            out, _ = greedy_generate(cfg, p, cache, first, prompt_len,
                                     gen_len - 1)
            jax.block_until_ready(out)
            marks[i][2] = time.time()

        marks[i][0] = time.time()
        task = Task(units=[UnitTask(fn=None, memobjs=frozenset({f"req{i}"}),
                                    resources=vec, name=f"req{i}")],
                    name=f"req{i}")
        handles.append(cluster.submit(
            ExecJob(job=Job(tasks=[task], name=f"req{i}"), runners=[runner]),
            deadline_s=deadline_s))

    cluster.drain()
    stats = cluster.stats()
    cluster.shutdown()
    if trace_path:
        cluster.export_trace(trace_path)
    wall = time.time() - t0
    done = [i for i, h in enumerate(handles) if h.status is JobStatus.DONE]
    # only real rows of completed batches count — a padded row generated
    # tokens nobody asked for, and a crashed/shed batch served none
    toks = sum(rows[i] for i in done) * gen_len
    # never-started records (crashed pre-launch) carry the NEVER_STARTED
    # sentinel, not a fake timestamp — they must not enter latency stats
    lat = [r.t_end - r.t_start
           for h in handles for r in h.records
           if not r.crashed and r.started]
    ttfts = [marks[i][1] - marks[i][0]
             for i in done for _ in range(rows[i]) if marks[i][1] >= 0]
    tpots = ([(marks[i][2] - marks[i][1]) / (gen_len - 1)
              for i in done for _ in range(rows[i]) if marks[i][2] >= 0]
             if gen_len > 1 else [])
    met = [h for h in handles if h.status is JobStatus.DONE
           and h.records and h.records[-1].t_end
           <= h.job.deadline_t]
    # shed requests (deadline passed while parked) are reported SEPARATELY
    # from deadlines_met: they consumed no device time at all, vs completed
    # requests that merely finished late
    shed = [h for h in handles if h.status is JobStatus.SHED]
    return {"requests": requests, "batches": n_batches,
            "tokens_generated": toks, "wall_s": wall,
            "tokens_per_s": toks / wall,
            "mean_batch_latency_s": float(np.mean(lat)) if lat else 0.0,
            "p50_ttft_s": _pct(ttfts, 0.50), "p99_ttft_s": _pct(ttfts, 0.99),
            "p50_tpot_s": _pct(tpots, 0.50), "p99_tpot_s": _pct(tpots, 0.99),
            "completed": stats["completed"], "crashed": stats["crashed"],
            "deadlines_met": len(met),
            "deadline_met_rate": len(met) / max(n_batches, 1),
            "shed": len(shed),
            "preemptions": stats["preemptions"],
            "migrations": stats["migrations"],
            "sched_attempts": stats["sched_attempts"],
            "placements": sched.placements}


def serve_continuous(arch: str, *, requests: int = 16, batch: int = 4,
                     prompt_len: int = 64, gen_len: int = 32, seed: int = 0,
                     num_devices: int = 0, workers: int = 0,
                     ttft_slo_s: float = 5.0, tpot_slo_s: float = 1.0,
                     shed_late: bool = False, full: bool = False,
                     prompt_lens=None, in_flight=None,
                     trace_path: str = None) -> dict:
    """Continuous-batching counterpart: per-request streaming through
    ServeEngine; ``batch`` becomes each decode loop's max rows.

    ``prompt_lens`` gives each request its own prompt length (default:
    ``requests`` prompts of ``prompt_len``). ``in_flight(cluster, engine)``
    runs once every request is submitted and before the drain: the hook
    for other work sharing the cluster while requests are in flight. The
    result's ``engine`` keeps the served requests (prompts, tokens,
    placements) and the model for checking them."""
    from repro.serve.engine import SLO, JaxModel, ServeEngine

    cfg, params = _model(arch, full, seed)
    lens = list(prompt_lens) if prompt_lens else [prompt_len] * requests
    num_devices, hbm = _fleet(num_devices)
    t_setup = time.time()
    model = JaxModel(cfg, params, max_batch=batch,
                     max_seq=max(lens) + gen_len, attn_impl="flash_jnp")
    cluster = Cluster(MGBAlg3Scheduler(num_devices, hbm_per_device=hbm),
                      workers=workers or num_devices, shed_late=shed_late,
                      trace=bool(trace_path))
    eng = ServeEngine(cluster, model, max_batch=batch,
                      slo=SLO(ttft_s=ttft_slo_s, tpot_s=tpot_slo_s))
    setup_s = time.time() - t_setup
    rng = np.random.default_rng(seed)
    t0 = time.time()
    for n in lens:
        eng.submit(prompt=jnp.asarray(rng.integers(
            0, cfg.vocab, (1, n), dtype=np.int32)),
            gen_len=gen_len)
    # submission probes each new prompt shape: its compile is set-up
    submit_s = time.time() - t0
    if in_flight is not None:
        in_flight(cluster, eng)
    t_serve = time.time()
    eng.drain(timeout_s=900.0)
    serve_s = time.time() - t_serve
    wall = time.time() - t0
    m = eng.metrics()
    eng.shutdown()
    cluster.drain()
    cluster.shutdown()
    if trace_path:
        cluster.export_trace(trace_path)
    m.update(wall_s=wall, setup_s=setup_s, submit_s=submit_s,
             serve_s=serve_s, tokens_per_s=m["tokens"] / wall,
             sched_attempts=cluster.stats()["sched_attempts"],
             engine=eng)
    return m


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b", choices=sorted(ARCHS))
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--num-devices", type=int, default=0,
                    help="scheduler devices (0 = one per attached device; "
                         "more is only legal on the CPU backend)")
    ap.add_argument("--full", action="store_true",
                    help="published config with bf16 weights (default: "
                         "the reduced CPU-sized variant)")
    ap.add_argument("--workers", type=int, default=0,
                    help="execution-pool size (0 = one per device)")
    ap.add_argument("--deadline-s", type=float, default=5.0,
                    help="per-request admission deadline (EDF ordering); "
                         "continuous mode reads it as the TTFT SLO")
    ap.add_argument("--tpot-slo-s", type=float, default=1.0,
                    help="continuous mode: time-per-output-token SLO")
    ap.add_argument("--shed-late", action="store_true",
                    help="fail requests still parked past their deadline "
                         "(JobStatus.SHED) instead of serving them late")
    ap.add_argument("--preempt", action="store_true",
                    help="preemptive EDF: an arriving earlier-deadline "
                         "request may evict a resident one (checkpoint-"
                         "based, work-conserving) instead of queueing "
                         "behind it (static mode only)")
    ap.add_argument("--trace", default=None, metavar="OUT_JSON",
                    help="record the scheduler's event stream and write a "
                         "Chrome/Perfetto trace-event JSON here at the end "
                         "(load in chrome://tracing or ui.perfetto.dev)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching via repro.serve.engine: "
                         "requests stream individually, decode batches "
                         "grow/shrink per step under scheduler admission")
    args = ap.parse_args()
    enable_compile_cache()
    if args.continuous:
        res = serve_continuous(
            args.arch, requests=args.requests, batch=args.batch,
            prompt_len=args.prompt_len, gen_len=args.gen_len,
            num_devices=args.num_devices, workers=args.workers,
            ttft_slo_s=args.deadline_s, tpot_slo_s=args.tpot_slo_s,
            shed_late=args.shed_late, full=args.full,
            trace_path=args.trace)
        print(f"[serve --continuous] {res['done']}/{res['requests']} done, "
              f"{res['tokens']} tokens in {res['wall_s']:.1f}s "
              f"({res['tokens_per_s']:.1f} tok/s, "
              f"TTFT p50/p99 {res['p50_ttft_s'] * 1e3:.0f}/"
              f"{res['p99_ttft_s'] * 1e3:.0f} ms, "
              f"TPOT p50/p99 {res['p50_tpot_s'] * 1e3:.0f}/"
              f"{res['p99_tpot_s'] * 1e3:.0f} ms, "
              f"goodput {res['goodput_rps']:.2f} req/s, "
              f"{res['shed']} shed, {res['violations']} memory violations)")
        return
    res = serve(args.arch, requests=args.requests, batch=args.batch,
                prompt_len=args.prompt_len, gen_len=args.gen_len,
                num_devices=args.num_devices, workers=args.workers,
                deadline_s=args.deadline_s, shed_late=args.shed_late,
                preempt=args.preempt, full=args.full, trace_path=args.trace)
    print(f"[serve] {res['tokens_generated']} tokens in {res['wall_s']:.1f}s "
          f"({res['tokens_per_s']:.1f} tok/s, "
          f"batch latency {res['mean_batch_latency_s'] * 1e3:.0f} ms, "
          f"TTFT p99 {res['p99_ttft_s'] * 1e3:.0f} ms, "
          f"TPOT p99 {res['p99_tpot_s'] * 1e3:.0f} ms, "
          f"{res['deadlines_met']}/{res['batches']} deadlines met "
          f"({100 * res['deadline_met_rate']:.0f}%), "
          f"{res['shed']} shed, {res['preemptions']} preemption(s), "
          f"{res['migrations']} migration(s), "
          f"{res['sched_attempts']} admission attempts)")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Chip smoke test: the live ``Cluster`` path end to end on a TPU, at full
width, through the entry points a user calls.

One chip (the default), in one process:

  1. serving — zamba2-2.7b at its published widths and full depth (54
     layers: Mamba2 blocks plus the shared attention block, vocab 32000,
     bf16 weights generated from ``--seed``) through
     ``repro.launch.serve.serve_continuous``: ``ServeEngine`` + ``JaxModel``
     over a live ``Cluster`` whose ``MGBAlg3Scheduler`` is built from the
     chip (device count from ``jax.devices()``, capacity from
     ``bytes_limit``). 8 requests, prompts of 128-512 tokens, 32 output
     tokens each, every one checked against a direct ``prefill`` +
     ``greedy_generate`` of its prompt on the same chip;
  2. training beside serving — while requests are in flight, a train job
     (zamba2-2.7b at full width, depth cut to one layer period: 5 Mamba2 +
     1 shared attention) of 3 AdamW steps is submitted to the same Cluster
     with a resource vector probed on the chip; the scheduler decides
     whether it co-resides or waits, and its losses must equal the same
     steps run directly;
  3. memory — per chip: the admitted reservations' high-water, the chip's
     own ``peak_bytes_in_use`` (at most ``bytes_limit``), and no
     RESOURCE_EXHAUSTED anywhere.

``--chips 4`` runs only the multi-chip path: one decode loop per chip
serving 16 requests placed by the scheduler (each loop's cache and each
prefill's outputs checked to live on the chip they were placed on, tokens
compared with a one-chip run of the same prompts), then one gang train step
(chips=4) on a mesh built from the reservation's devices, compared with
the same step on one chip.

Any failure exits non-zero, and so does a run that finds no TPU: there is
no CPU fallback. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

    python chip_smoke.py [--chips 4] [--seed 0]
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

ARCH = "zamba2-2.7b"
GEN_LEN = 32                  # output tokens per request, incl. the first
MAX_BATCH = 8                 # rows per decode loop
TRAIN_STEPS = 3
TRAIN_BATCH, TRAIN_SEQ = 4, 128
# Token checks. Two direct references run each request outside the engine
# and the scheduler, from the same prefill executable the engine ran:
#  * a replay: the decode step the engine runs (the same program, at the
#    same MAX_BATCH rows and per-row positions) called greedily step by
#    step. The serving path must reproduce it token for token: a difference
#    is a fault in rows, positions, cache hand-over or placement.
#  * greedy_generate: the same decode_step inside a scan, which is another
#    program. Its bf16 rounding may differ, and in this random-weight model
#    a rounding difference grows through 54 layers and the recurrent state
#    (against a batch-1 program on the chip the logits drifted by up to
#    0.125 and argmax flipped within 5 tokens). Its tokens must equal the
#    replay's up to their first divergence, which must be a near-tie: the
#    replay's logits for the two tokens within NEAR_TIE of each other
#    (twice that observed drift). Past it the sequences are not compared.
NEAR_TIE = 0.25
# the gang step shards the batch and the loss reduction over 4 chips: the
# same arithmetic summed in another order
GANG_LOSS_RTOL = 1e-3


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(msg, flush=True)


def gb(n):
    return f"{n / 1e9:.3f} GB"


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def tree_bytes(tree):
    import jax
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def leaf_devices(tree):
    import jax
    return {d for x in jax.tree_util.tree_leaves(tree) for d in x.devices()}


def prompt_lens(n):
    # Mamba2's SSD chunk is 256: a prompt is shorter or a whole number of
    # chunks
    return [(128, 256, 512)[i % 3] for i in range(n)]


def reference_tokens(model, reqs, device):
    """Both direct references (see NEAR_TIE) for up to MAX_BATCH requests:
    each prompt through the prefill executable, its cache written into one
    row of a MAX_BATCH-row cache, then the replay and one greedy_generate
    call over all rows, each at its own position. Rows are filled in
    reverse request order, so a request usually sits in another row than
    the engine gave it: a match also shows that rows do not interact.

    Returns {rid: (replay tokens, greedy_generate tokens, the replay's
    logits at each decode step)}."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import decode as D

    cfg = model.cfg
    params = model.params_on(device)
    with jax.default_device(device):
        cache = jax.device_put(D.init_cache(cfg, MAX_BATCH, model.max_seq),
                               device)
    first = np.zeros((MAX_BATCH,), np.int32)
    pos = np.zeros((MAX_BATCH,), np.int32)
    rows = {}
    for row, r in zip(range(MAX_BATCH - 1, -1, -1), reqs):
        logits, c = model._prefill(
            params, {"tokens": jax.device_put(r.prompt, device)})
        first[row] = int(jnp.argmax(logits[0]))
        pos[row] = r.prompt_len
        cache = model._insert(cache, c, row)
        rows[r.rid] = row
    greedy, step = _ref_fns(cfg)

    def put(a):
        return jax.device_put(a, device)
    gen = np.asarray(greedy(params, cache, put(first), put(pos))[0])
    tok, replay, logits = first, [first], []
    for i in range(GEN_LEN - 1):
        lg, cache = step(params, cache, put(tok), put(pos + i))
        tok = np.asarray(jnp.argmax(lg, axis=-1).astype(jnp.int32))
        replay.append(tok)
        logits.append(lg)
    replay = np.stack(replay)                      # [GEN_LEN, rows]
    return {rid: ([int(x) for x in replay[:, row]],
                  [int(first[row])] + [int(x) for x in gen[row]],
                  [lg[row] for lg in logits])
            for rid, row in rows.items()}


_REF_FNS = {}


def _ref_fns(cfg):
    """Jitted greedy_generate (GEN_LEN - 1 steps) and single decode step,
    one pair per config."""
    import jax
    from repro.serve.decode import greedy_generate, make_serve_step
    if cfg not in _REF_FNS:
        _REF_FNS[cfg] = (
            jax.jit(lambda p, c, f, pos: greedy_generate(
                cfg, p, c, f, pos, GEN_LEN - 1)),
            jax.jit(make_serve_step(cfg)))
    return _REF_FNS[cfg]


def compare_generate(replay, gen, logits):
    """(exact, note): greedy_generate's tokens against the replay's — equal,
    or equal up to a first divergence at a near-tie; raises otherwise."""
    check(gen[0] == replay[0], f"first token {gen[0]} != {replay[0]} (same "
                               "prefill executable: must be exact)")
    for t in range(1, len(replay)):
        if gen[t] == replay[t]:
            continue
        lg = logits[t - 1]
        gap = abs(float(lg[gen[t]]) - float(lg[replay[t]]))
        check(gap <= NEAR_TIE,
              f"token {t}: greedy_generate {gen[t]}, replay {replay[t]}; "
              f"logit gap {gap:.4g} exceeds the near-tie bound {NEAR_TIE}")
        return False, f"near-tie at token {t} (gap {gap:.3g})"
    return True, "exact"


def check_served(res, device, label):
    """Every request DONE, its tokens equal to the direct replay, and the
    replay equal to greedy_generate up to a near-tie."""
    eng = res["engine"]
    failed = [r for r in eng.requests if r.status.value != "done"]
    check(not failed, f"{label}: {len(failed)} request(s) not done: "
                      f"{[(r.rid, r.status.value, r.error) for r in failed]}")
    check(res["violations"] == 0,
          f"{label}: {res['violations']} memory violations")
    t0 = time.time()
    ref = {}
    for i in range(0, len(eng.requests), MAX_BATCH):
        ref.update(reference_tokens(eng.model,
                                    eng.requests[i:i + MAX_BATCH], device))
    exact = 0
    for r in eng.requests:
        replay, gen, logits = ref[r.rid]
        diff = [t for t, (a, b) in enumerate(zip(r.tokens, replay)) if a != b]
        check(len(r.tokens) == len(replay) and not diff,
              f"{label}: request {r.rid} served {r.tokens}, direct replay "
              f"{replay} (first difference at token {diff[:1]})")
        same, note = compare_generate(replay, gen, logits)
        exact += same
        log(f"  request {r.rid}: prompt {r.prompt_len}, {len(r.tokens)} "
            f"tokens, loop device {r.device}: equal to the replay; "
            f"greedy_generate {note}")
    log(f"[{label}] {len(eng.requests)}/{len(eng.requests)} requests "
        f"token-equal to the direct replay; {exact} also to greedy_generate,"
        f" the rest up to a near-tie (references {time.time() - t0:.1f}s)")
    return {r.rid: r.tokens for r in eng.requests}


def train_parts(cfg, seed):
    """(depth-cut config, train step, initial-state function) for the
    train job: full width, one layer period deep."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import init_params
    from repro.optim import adamw
    from repro.train.train_step import make_train_step

    tcfg = dataclasses.replace(cfg, n_layers=cfg.hybrid_shared_every)
    opt_cfg = adamw.AdamWConfig(warmup_steps=1, total_steps=TRAIN_STEPS,
                                moment_dtype=tcfg.optimizer_moment_dtype)
    raw = make_train_step(tcfg, opt_cfg)

    def init_state(device=None):
        with jax.default_device(device):
            params = init_params(tcfg, jax.random.PRNGKey(seed + 1),
                                 param_dtype=jnp.bfloat16)
            opt = adamw.init_state(opt_cfg, params)
            tok = jax.random.randint(jax.random.PRNGKey(seed + 2),
                                     (TRAIN_BATCH, TRAIN_SEQ), 0, tcfg.vocab)
        state = (params, opt, {"tokens": tok,
                               "labels": jnp.roll(tok, -1, axis=1)})
        return state if device is None else jax.device_put(state, device)
    return tcfg, raw, init_state


def memory_report(sched, devices, label):
    """Per chip: reserved high-water vs the chip's own peak; the peak must
    stay within bytes_limit."""
    for ds, dev in zip(sched.devices, devices):
        st = dev.memory_stats()
        peak, limit = st["peak_bytes_in_use"], st["bytes_limit"]
        log(f"[{label}] chip {dev.id}: reserved high-water {gb(ds.peak_hbm)}"
            f", observed peak_bytes_in_use {gb(peak)}, bytes_limit "
            f"{gb(limit)}")
        check(peak <= limit, f"chip {dev.id}: peak {peak} > limit {limit}")


def no_exhaustion(*texts):
    for t in texts:
        check("RESOURCE_EXHAUSTED" not in (t or ""),
              f"RESOURCE_EXHAUSTED: {t}")


# ---------------------------------------------------------------------------
# one chip: serving + co-resident training + memory
# ---------------------------------------------------------------------------

def one_chip(seed, full=True):
    import jax
    from repro.core.cluster import JobStatus
    from repro.core.executor import ExecJob
    from repro.core.probe import probe_fn
    from repro.core.task import Job, Task, UnitTask
    from repro.launch.serve import serve_continuous

    dev = jax.devices()[0]
    train = {}
    # the train job is compiled and probed on the chip before any request
    # is submitted, so that it joins while requests are still in flight
    cfg = _serve_cfg(full)
    tcfg, step_raw, init_state = train_parts(cfg, seed)
    step = jax.jit(step_raw, donate_argnums=(0, 1))
    log(f"[train] {tcfg.name}: d_model {tcfg.d_model}, vocab "
        f"{tcfg.vocab}, depth cut {cfg.n_layers} -> {tcfg.n_layers} layers "
        f"(one period: {tcfg.hybrid_shared_every - 1} Mamba2 + 1 shared "
        f"attention), batch {TRAIN_BATCH} x seq {TRAIN_SEQ}, {TRAIN_STEPS} "
        "AdamW steps")
    t0 = time.time()
    vec = probe_fn(step, *jax.eval_shape(init_state), work_scale=TRAIN_STEPS)
    log(f"[train] probed on the chip in {time.time() - t0:.1f}s: reserves "
        f"{gb(vec.hbm_bytes)}, est {vec.est_seconds:.3f}s")

    def in_flight(cluster, eng):
        """Requests are in flight: submit the train job beside them, then
        report the serving reservations and check placements."""
        def runner(device):
            ds = cluster.sched.devices[0]
            train["co"] = collections.Counter(
                t.name.split("/")[0] for t in ds.residents.values()
                if t.name != "train")
            params, opt, batch = init_state(device)
            losses = []
            for _ in range(TRAIN_STEPS):
                params, opt, m = step(params, opt, batch)
                losses.append(m["loss"])
            train["losses"] = [float(x) for x in losses]

        train["inflight"] = sum(1 for r in eng.requests
                                if r.status.value != "done")
        unit = UnitTask(fn=None, memobjs=frozenset({"train"}), resources=vec,
                        name="train")
        job = Job(tasks=[Task(units=[unit], name="train")], name="train")
        train["handle"] = cluster.submit(ExecJob(job=job, runners=[runner]),
                                         priority=0)
        model = eng.model
        wbytes = tree_bytes(model.params)
        log(f"[serve] weights {gb(wbytes)} ({model.cfg.param_count() / 1e9:.3f}"
            f"B params, bf16)" if full else f"[serve] weights {gb(wbytes)}")
        for lp in eng.loops.values():
            log(f"[serve] decode-loop/{lp.device}: reserved "
                f"{gb(lp.host.resources.hbm_bytes)} (weights {gb(wbytes)} + "
                f"workspace; + {gb(model.slot_bytes)} per joined row)")
        for r in eng.requests[:3]:
            log(f"[serve] prefill/{r.rid} (prompt {r.prompt_len}): reserved "
                f"{gb(model.prefill_vec(r).hbm_bytes)} (weights "
                f"{gb(wbytes)} counted as a compiled argument)")
        # placements: every loop's cache lives on its chip
        for lp in eng.loops.values():
            want = cluster.jax_device(lp.device)
            check(leaf_devices(lp.state["cache"]) == {want},
                  f"decode-loop/{lp.device} cache not on {want}")

    t0 = time.time()
    res = serve_continuous(ARCH, full=full, prompt_lens=prompt_lens(8),
                           batch=MAX_BATCH, gen_len=GEN_LEN, seed=seed,
                           workers=4, ttft_slo_s=600.0, tpot_slo_s=60.0,
                           in_flight=in_flight)
    serve_wall = time.time() - t0
    eng = res["engine"]
    log(f"[serve] set-up + decode compile {res['setup_s']:.1f}s, submit "
        f"(prefill probe compiles) {res['submit_s']:.1f}s, serving "
        f"{res['serve_s']:.1f}s; {res['tokens']} tokens served, "
        f"{res['done']}/{res['requests']} done, TTFT p50 "
        f"{res['p50_ttft_s']:.3f}s, TPOT p50 {res['p50_tpot_s'] * 1e3:.1f}"
        f" ms (total {serve_wall:.1f}s)")
    for r in eng.requests:
        check(r.prefill_device == dev and r.prefill_devices == {dev},
              f"prefill/{r.rid} ran on {r.prefill_devices}, placed on "
              f"{r.prefill_device}")
    log(f"[serve] placements: prefills on {dev}, slot joins "
        f"{eng.join_log}")
    no_exhaustion(*(r.error for r in eng.requests))
    outs = check_served(res, dev, "serve")

    h = train["handle"]
    no_exhaustion(h.job.error)
    check(h.status is JobStatus.DONE,
          f"train job {h.status.value}: {h.job.error}")
    rec = h.records[-1]
    wait = rec.t_start - rec.t_queue
    co = dict(train["co"])
    log(f"[train] submitted with {train['inflight']} request(s) in flight; "
        f"scheduler decision: "
        + (f"co-resides with {co} (resident tasks by kind)" if co else
           "waited for the chip to drain")
        + f" (parked {wait:.2f}s, ran {rec.t_end - rec.t_start:.2f}s)")
    losses = train["losses"]
    t0 = time.time()
    params, opt, batch = init_state(dev)
    direct = []
    for _ in range(TRAIN_STEPS):
        params, opt, m = step(params, opt, batch)
        direct.append(float(m["loss"]))
    del params, opt, batch
    log(f"[train] scheduled losses {losses}; direct {direct} "
        f"({time.time() - t0:.1f}s)")
    check(all(math.isfinite(x) for x in losses),
          f"non-finite train loss {losses}")
    check(all(abs(a - b) <= 1e-5 * max(abs(b), 1.0)
              for a, b in zip(losses, direct)),
          f"scheduled losses {losses} != direct {direct}")
    memory_report(eng.sched, [dev], "memory")
    return {"tokens": outs, "train_losses": losses,
            "tokens_served": res["tokens"]}


# ---------------------------------------------------------------------------
# four chips: per-chip loops + gang train step
# ---------------------------------------------------------------------------

def four_chip(seed, full=True):
    import jax
    from repro.core.cluster import Cluster, JobStatus
    from repro.core.executor import ExecJob, device_capacity
    from repro.core.probe import probe_fn
    from repro.core.scheduler import GangScheduler
    from repro.core.task import Job, Task, UnitTask
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist import sharding as SH
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import serve_continuous

    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    lens = prompt_lens(16)

    def placements(cluster, eng):
        check(len(eng.loops) == 4, f"{len(eng.loops)} decode loops")
        for lp in eng.loops.values():
            want = cluster.jax_device(lp.device)
            check(leaf_devices(lp.state["cache"]) == {want},
                  f"decode-loop/{lp.device} cache not on {want}")
            log(f"[4chip] decode-loop/{lp.device}: cache on {want}")

    t0 = time.time()
    res4 = serve_continuous(ARCH, full=full, prompt_lens=lens,
                            batch=MAX_BATCH, gen_len=GEN_LEN, seed=seed,
                            workers=4, ttft_slo_s=600.0, tpot_slo_s=60.0,
                            in_flight=placements)
    eng = res4["engine"]
    log(f"[4chip] serve: set-up {res4['setup_s']:.1f}s, submit "
        f"{res4['submit_s']:.1f}s, serving {res4['serve_s']:.1f}s, "
        f"{res4['tokens']} tokens, {res4['done']}/{res4['requests']} done "
        f"(total {time.time() - t0:.1f}s)")
    for r in eng.requests:
        check(r.status.value == "done", f"request {r.rid} {r.status.value}"
                                        f": {r.error}")
        check(r.prefill_devices == {r.prefill_device},
              f"prefill/{r.rid} outputs on {r.prefill_devices}, placed on "
              f"{r.prefill_device}")
    by_chip = {}
    for rid, d in eng.join_log:
        by_chip.setdefault(d, []).append(rid)
    log(f"[4chip] rows per chip {by_chip}; prefill chips "
        f"{sorted({r.prefill_device.id for r in eng.requests})}")
    check(res4["violations"] == 0, "memory violations")
    memory_report(eng.sched, devs, "4chip")
    toks4 = {i: list(r.tokens) for i, r in enumerate(eng.requests)}
    del eng, res4
    gc.collect()

    t0 = time.time()
    res1 = serve_continuous(ARCH, full=full, prompt_lens=lens,
                            batch=MAX_BATCH, gen_len=GEN_LEN, seed=seed,
                            num_devices=1, workers=4, ttft_slo_s=600.0,
                            tpot_slo_s=60.0)
    toks1 = {i: list(r.tokens) for i, r in enumerate(res1["engine"].requests)}
    exact = sum(toks1[i] == toks4[i] for i in toks4)
    log(f"[4chip] one-chip run of the same 16 prompts: {exact}/16 requests "
        f"token-equal ({time.time() - t0:.1f}s)")
    check_served(res1, devs[0], "4chip/one-chip")
    del res1
    gc.collect()

    # one gang train step on the reservation's mesh vs one chip
    tcfg, step_raw, init_state = train_parts(_serve_cfg(full), seed)
    step = jax.jit(step_raw)
    n, hbm = device_capacity()
    sched = GangScheduler(1, 2, 2, hbm_per_chip=hbm)
    vec = dataclasses.replace(
        probe_fn(step, *jax.eval_shape(init_state)), chips=4)
    log(f"[gang] train step reserves {gb(vec.hbm_bytes)} over 4 chips "
        f"({gb(vec.hbm_bytes // 4)} per chip)")
    state = init_state(devs[0])
    out = {}

    def runner(devices):
        mesh = make_mesh((4, 1), ("data", "model"), devices=devices)
        params, opt, batch = state
        with SH.activation_mesh(mesh):
            psh = SH.to_named(SH.param_specs(tcfg, params, mesh), mesh)
            bsh = SH.to_named(SH.batch_specs(tcfg, batch, mesh), mesh)
            p = jax.device_put(params, psh)
            o = {"mu": jax.device_put(opt["mu"], psh),
                 "nu": jax.device_put(opt["nu"], psh),
                 "step": jax.device_put(opt["step"],
                                        NamedSharding(mesh, P()))}
            b = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
            _, _, m = jax.jit(step_raw)(p, o, b)
            out["loss"] = float(m["loss"])
        out["devices"] = [d.id for d in devices]
        out["param_devices"] = sorted(d.id for d in leaf_devices(p))

    unit = UnitTask(fn=None, memobjs=frozenset({"gang"}), resources=vec,
                    name="gang-train")
    with Cluster(sched, workers=1) as cluster:
        h = cluster.submit(ExecJob(
            job=Job(tasks=[Task(units=[unit], name="gang-train")],
                    name="gang-train"), runners=[runner]))
        h.result(timeout=900)
    no_exhaustion(h.job.error)
    check(h.status is JobStatus.DONE, f"gang job {h.status.value}: "
                                      f"{h.job.error}")
    check(sorted(out["devices"]) == sorted(d.id for d in devs),
          f"gang ran on {out['devices']}")
    check(out["param_devices"] == sorted(d.id for d in devs),
          f"gang params on {out['param_devices']}")
    _, _, m = step(*state)
    single = float(m["loss"])
    log(f"[gang] chips {out['devices']}: loss {out['loss']!r}; one chip "
        f"{single!r}")
    check(abs(out["loss"] - single) <= GANG_LOSS_RTOL * max(abs(single), 1),
          f"gang loss {out['loss']} != single-chip {single}")
    # last, so that a mismatch still leaves every other result above
    check(exact == len(toks4), "4-chip tokens differ from the one-chip run")
    return {"tokens": toks4, "gang_loss": out["loss"],
            "single_loss": single}


def _serve_cfg(full):
    from repro.configs.registry import get_arch
    cfg = get_arch(ARCH)
    return cfg if full else cfg.reduced()


# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chip_smoke: no accelerator: {e}")
    d0 = devs[0]
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found {d0.platform} "
                 f"({d0.device_kind}); no CPU fallback")
    log(f"[device] platform {d0.platform}, kind {d0.device_kind!r}, "
        f"count {len(devs)}, bytes_limit "
        f"{gb(d0.memory_stats()['bytes_limit'])}; compile cache {cache_dir}")
    t0 = time.time()
    if args.chips == 4:
        four_chip(args.seed)
    else:
        one_chip(args.seed)
    log(f"[done] all phases passed in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    try:
        main()
    except SmokeFailure as e:
        sys.exit(f"chip_smoke FAILED: {e}")

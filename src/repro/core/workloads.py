"""Workload generation for the paper's evaluation (§V-A).

Rodinia-analogue jobs: a library of kernel families with the same resource
personalities as the paper's picks (backprop, srad v1/v2, lavaMD, needle,
dwt2d, bfs) expressed as pure-JAX computations. Each job's ResourceVector is
obtained the compiler-guided way — ``jit(fn).lower(ShapeDtypeStruct...).
compile()`` and probing the artifact (no allocation, so we probe at FULL
multi-GB footprints even on this CPU container). Durations are the roofline
estimate scaled by an iteration count calibrated to the paper's 5-10-minute
workloads.

Mixes (Table I): large = >4 GB footprint, small = 1-4 GB; W1..W8 are
{16, 32} jobs x {1:1, 2:1, 3:1, 5:1} large:small, randomly drawn but seeded.

NN jobs (§V-E): predict / train / detect / generate personalities probed from
THIS repo's real model substrate (prefill / train_step / decode of reduced
archs) — each network 0.5-1.5 GB, detect deliberately low-utilization
(nvidia-smi reported <=25% for yolo with MULTIPLE jobs resident, i.e.
<=1/8 per job — demands are calibrated to the paper's own utilization data).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chips import V5E
from repro.core.probe import probe_fn
from repro.core.task import Job, ResourceVector, Task, UnitTask

GB = 1024**3


# ---------------------------------------------------------------------------
# Rodinia-analogue kernel library
# ---------------------------------------------------------------------------
# Each entry: (fn(n) kernel over an n-element working set, bytes-per-n,
# personality notes). All fns are jittable; probes run on ShapeDtypeStructs.

def _k_backprop(x, w1, w2):
    """2-layer MLP fwd+bwd over a chunk (pattern recognition)."""
    def loss(w1, w2):
        h = jnp.tanh(x @ w1)
        return jnp.sum(jnp.square(h @ w2))
    g1, g2 = jax.grad(loss, argnums=(0, 1))(w1, w2)
    return w1 - 1e-3 * g1, w2 - 1e-3 * g2


def _k_srad(img):
    """Anisotropic diffusion stencil sweep (image processing)."""
    def step(im, _):
        n = jnp.roll(im, 1, 0) + jnp.roll(im, -1, 0) \
            + jnp.roll(im, 1, 1) + jnp.roll(im, -1, 1) - 4 * im
        g = n / (im + 1e-6)
        c = 1.0 / (1.0 + jnp.square(g))
        return im + 0.1 * c * n, None
    out, _ = jax.lax.scan(step, img, None, length=8)
    return out


def _k_lavamd(pos, q):
    """All-pairs-in-neighborhood force kernel (molecular dynamics)."""
    def cell(p_block):
        d = p_block[:, None, :] - p_block[None, :, :]   # [c, c, 3]
        r2 = jnp.sum(d * d, axis=-1) + 1e-3
        f = q[:, None] * q[None, :] / r2
        return jnp.sum(f[..., None] * d, axis=1)
    return jax.vmap(cell)(pos)


def _k_needle(seq):
    """Wavefront DP over an alignment matrix (bioinformatics)."""
    def row(prev, s):
        cur = jnp.maximum(prev + s, jnp.roll(prev, 1) - 1.0)
        return cur, cur
    _, rows = jax.lax.scan(row, seq[0], seq)
    return rows


def _k_dwt2d(img):
    """Separable wavelet transform passes (image/video compression)."""
    lo = (img[:, ::2] + img[:, 1::2]) * 0.5
    hi = (img[:, ::2] - img[:, 1::2]) * 0.5
    lo2 = (lo[::2] + lo[1::2]) * 0.5
    hi2 = (lo[::2] - lo[1::2]) * 0.5
    return lo2, hi2, hi


def _k_bfs(adj, frontier):
    """Sparse frontier expansion as dense matvec rounds (graph)."""
    def step(f, _):
        nf = jnp.clip(adj @ f, 0.0, 1.0)
        return nf, jnp.sum(nf)
    out, sums = jax.lax.scan(step, frontier, None, length=4)
    return out, sums


# Achieved-efficiency profiles (core_eff, bw_eff): the fraction of peak
# compute / HBM bandwidth each family reaches while running solo. Dense
# matmuls run near the MXU roof; stencils reach ~half of stream bandwidth;
# wavefront DP and graph frontier expansion are latency-bound. Calibrated to
# the paper's motivating observation that a typical workload uses ~30% of a
# device (§I) — the mixes below average ~=0.35 dominant-resource share.
EFFICIENCY = {
    "backprop": (0.85, 0.60),
    "srad_v1": (0.50, 0.45),
    "srad_v2": (0.50, 0.45),
    "lavamd": (0.90, 0.50),
    "needle": (0.30, 0.25),
    "dwt2d": (0.40, 0.35),
    "bfs": (0.25, 0.20),
}


def _probe_at(family: str, n: int) -> ResourceVector:
    """Probe one kernel family at an n-element working set (no allocation)."""
    S = jax.ShapeDtypeStruct
    f32 = jnp.float32
    eff = EFFICIENCY[family]
    if family == "backprop":
        d = max(int((n / 6) ** 0.5) // 128 * 128, 256)
        return probe_fn(_k_backprop, S((d, d), f32), S((d, d), f32),
                        S((d, d), f32), efficiency=eff)
    if family in ("srad_v1", "srad_v2"):
        side = max(int((n / 2) ** 0.5) // 128 * 128, 256)
        return probe_fn(_k_srad, S((side, side), f32), efficiency=eff)
    if family == "lavamd":
        cells_ = max(n // (4 * 128), 64)
        return probe_fn(_k_lavamd, S((cells_, 128, 3), f32), S((128,), f32),
                        efficiency=eff)
    if family == "needle":
        side = max(int((n / 2) ** 0.5) // 128 * 128, 256)
        return probe_fn(_k_needle, S((side, side), f32), efficiency=eff)
    if family == "dwt2d":
        side = max(int((n / 2) ** 0.5) // 128 * 128, 256)
        return probe_fn(_k_dwt2d, S((side, side), f32), efficiency=eff)
    if family == "bfs":
        side = max(int(n ** 0.5) // 128 * 128, 256)
        return probe_fn(_k_bfs, S((side, side), f32), S((side,), f32),
                        efficiency=eff)
    raise KeyError(family)


@functools.lru_cache(maxsize=None)
def _probe_family(family: str, footprint_bytes: int) -> ResourceVector:
    """Probe a kernel family, CALIBRATING the working-set size until the
    compiled footprint (args + temps, which the nominal size underestimates)
    lands within 25% of the target. Footprint is ~linear in n, so 1-3
    fixed-point steps converge."""
    n = footprint_bytes // 4
    vec = _probe_at(family, n)
    for _ in range(3):
        ratio = vec.hbm_bytes / footprint_bytes
        if 0.75 <= ratio <= 1.25:
            break
        n = max(int(n / ratio), 1 << 16)
        vec = _probe_at(family, n)
    return vec


# paper: 7 combos at 1-4 GB (all but lavaMD), 10 combos > 4 GB (all but bfs)
SMALL_FAMILIES = ["backprop", "srad_v1", "srad_v2", "needle", "dwt2d", "bfs"]
LARGE_FAMILIES = ["backprop", "srad_v1", "srad_v2", "lavamd", "needle",
                  "dwt2d"]
SMALL_RANGE = (1.0 * GB, 4.0 * GB)
LARGE_RANGE = (4.5 * GB, 13.0 * GB)
# calibrate job durations to the paper's 5-10-minute workload scale
TARGET_JOB_SECONDS = (8.0, 40.0)


def make_rodinia_job(rng: np.random.Generator, *, large: bool,
                     name: str) -> Job:
    fam = rng.choice(LARGE_FAMILIES if large else SMALL_FAMILIES)
    lo, hi = LARGE_RANGE if large else SMALL_RANGE
    # snap footprints to a small grid so the probe cache hits
    foot = int(rng.uniform(lo, hi) / (0.5 * GB)) * int(0.5 * GB)
    base = _probe_family(str(fam), foot)
    tgt = rng.uniform(*TARGET_JOB_SECONDS)
    vec = base.scaled(tgt / max(base.est_seconds, 1e-9))
    unit = UnitTask(fn=None, memobjs=frozenset({f"{name}/ws"}),
                    resources=vec, name=f"{fam}-{foot // GB}G")
    return Job(tasks=[Task(units=[unit], name=unit.name)], name=name)


def make_mix(seed: int, n_jobs: int, ratio: Tuple[int, int]) -> List[Job]:
    """ratio = (large, small), e.g. (3, 1). Jobs randomly drawn, seeded."""
    rng = np.random.default_rng(seed)
    lg, sm = ratio
    jobs = []
    for i in range(n_jobs):
        large = (i % (lg + sm)) < lg
        jobs.append(make_rodinia_job(rng, large=large, name=f"job{i:03d}"))
    order = rng.permutation(len(jobs))
    return [jobs[i] for i in order]


# Table I: the eight Rodinia workloads
WORKLOADS: Dict[str, Tuple[int, Tuple[int, int]]] = {
    "W1": (16, (1, 1)), "W2": (16, (2, 1)), "W3": (16, (3, 1)),
    "W4": (16, (5, 1)), "W5": (32, (1, 1)), "W6": (32, (2, 1)),
    "W7": (32, (3, 1)), "W8": (32, (5, 1)),
}


def workload(name: str, seed: int = 0) -> List[Job]:
    n, ratio = WORKLOADS[name]
    # stable per-workload seed (python hash() is salted per process)
    tag = sum(ord(c) * 31 ** i for i, c in enumerate(name)) % 1000
    return make_mix(seed + tag, n, ratio)


# ---------------------------------------------------------------------------
# NN jobs (§V-E) — probed from this repo's real model substrate
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _nn_vector(kind: str) -> ResourceVector:
    from repro.configs.registry import get_arch
    from repro.launch.flops import forward_flops, step_flops
    from repro.configs.base import ShapeConfig
    from repro.optim.adamw import AdamWConfig
    from repro.serve.decode import make_prefill_step
    from repro.train.train_step import abstract_train_state, make_train_step

    if kind == "predict":   # darknet19/53 classification: prefill-like
        cfg = get_arch("qwen1.5-32b").reduced()
        shape = ShapeConfig("nn_predict", 1024, 8, "prefill")
        step = make_prefill_step(cfg, attn_impl="flash_jnp")
        params, _ = abstract_train_state(cfg, AdamWConfig())
        from repro.launch.specs import input_specs
        batch = input_specs(cfg, shape)
        compiled = jax.jit(step).lower(params, batch).compile()
        from repro.core.probe import vector_from_compiled
        return vector_from_compiled(
            compiled, flops_override=forward_flops(cfg, 8, 1024),
            work_scale=400.0, efficiency=(0.18, 0.15))
    if kind == "train":     # CIFAR-small training
        cfg = get_arch("gemma2-9b").reduced()
        shape = ShapeConfig("nn_train", 512, 16, "train")
        opt = AdamWConfig()
        step = make_train_step(cfg, opt, attn_impl="flash_jnp")
        params, opts = abstract_train_state(cfg, opt)
        from repro.launch.specs import input_specs
        batch = input_specs(cfg, shape)
        compiled = jax.jit(step).lower(params, opts, batch).compile()
        from repro.core.probe import vector_from_compiled
        return vector_from_compiled(
            compiled, flops_override=step_flops(cfg, shape),
            work_scale=250.0, efficiency=(0.39, 0.30))
    if kind == "detect":    # yolo real-time: tiny, low utilization (<=25%)
        import dataclasses as _dc
        base = _nn_vector("predict")
        return _dc.replace(base.scaled(0.5), core_demand=0.12,
                           bw_demand=0.10, hbm_bytes=int(0.6 * GB))
    if kind == "generate":  # RNN text generation: decode-step personality
        from repro.serve.decode import abstract_cache, make_serve_step
        cfg = get_arch("musicgen-large").reduced()
        serve = make_serve_step(cfg)
        params, _ = abstract_train_state(cfg, AdamWConfig())
        cache = abstract_cache(cfg, 8, 512)
        tok = jax.ShapeDtypeStruct((8,), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        compiled = jax.jit(serve).lower(params, cache, tok, pos).compile()
        from repro.core.probe import vector_from_compiled
        return vector_from_compiled(compiled, work_scale=20000.0,
                                    efficiency=(0.05, 0.275))
    raise KeyError(kind)


NN_KINDS = ("predict", "train", "detect", "generate")
# paper: each NN's device state is 0.5-1.5 GB
_NN_MEM = {"predict": int(1.1 * GB), "train": int(1.5 * GB),
           "detect": int(0.6 * GB), "generate": int(0.5 * GB)}


def make_nn_job(kind: str, idx: int) -> Job:
    import dataclasses as _dc
    vec = _dc.replace(_nn_vector(kind), hbm_bytes=_NN_MEM[kind])
    unit = UnitTask(fn=None, memobjs=frozenset({f"nn{idx}/{kind}"}),
                    resources=vec, name=f"{kind}{idx}")
    return Job(tasks=[Task(units=[unit], name=unit.name)], name=f"{kind}{idx}")


def nn_homogeneous(kind: str, n_jobs: int = 8) -> List[Job]:
    return [make_nn_job(kind, i) for i in range(n_jobs)]


def nn_mix(seed: int, n_jobs: int = 128) -> List[Job]:
    rng = np.random.default_rng(seed)
    return [make_nn_job(str(rng.choice(NN_KINDS)), i) for i in range(n_jobs)]


# ---------------------------------------------------------------------------
# Gang workloads — multi-chip tasks for the gang placement subsystem
# ---------------------------------------------------------------------------
# A gang job is one Task with resources.chips = k: a sharded train step (or
# pipeline stage group) whose k shards run in lockstep on a contiguous device
# group. Convention (matches GangScheduler): ``hbm_bytes`` is the TOTAL
# footprint (charged per chip as hbm_bytes / chips), ``core_demand`` /
# ``bw_demand`` are per-chip shares, ``collective_bytes`` is the per-link
# ring payload its collectives move over the group's ICI links, and
# ``est_seconds`` is the roofline max of compute and ICI-collective time.
# Vectors are synthetic (seeded) rather than probed: a gang has no single
# compiled artifact to probe yet — the per-shard executable exists, but the
# group personality (collective share, lockstep duration) is a property of
# the sharding, which these knobs model directly.

# synthetic flops/bytes numbers are sized against the v5e's peaks

def make_gang_job(rng: np.random.Generator, *, chips: int, name: str,
                  per_chip_gb: Tuple[float, float] = (2.0, 6.0),
                  seconds: Tuple[float, float] = TARGET_JOB_SECONDS,
                  collective_share: Tuple[float, float] = (0.25, 0.6)) -> Job:
    """One k-chip gang job: seeded per-chip footprint/demand, a compute
    duration, and a collective payload sized so its steady ICI-link share
    lands in ``collective_share`` (the knob link contention studies turn)."""
    per_chip = rng.uniform(*per_chip_gb) * GB
    compute_s = rng.uniform(*seconds)
    share = rng.uniform(*collective_share)
    demand = rng.uniform(0.4, 0.9)
    # per-link ring payload that occupies `share` of a link for compute_s
    collective_bytes = share * compute_s * V5E.ici_bw
    est = max(compute_s, collective_bytes / V5E.ici_bw)  # = compute_s (share<=1)
    vec = ResourceVector(
        hbm_bytes=int(per_chip * chips),
        flops=demand * compute_s * V5E.flops * chips,
        bytes_accessed=0.5 * demand * compute_s * V5E.hbm_bw * chips,
        collective_bytes=collective_bytes,
        est_seconds=est, core_demand=demand, bw_demand=0.5 * demand,
        chips=chips)
    unit = UnitTask(fn=None, memobjs=frozenset({f"{name}/shards"}),
                    resources=vec, name=name)
    task = Task(units=[unit], name=name, gang_id=name)
    return Job(tasks=[task], name=name, gang_id=name)


def gang_mix(seed: int, *, n_singles: int = 12, n_gangs: int = 8,
             chip_choices: Sequence[int] = (2, 4),
             probe_singles: bool = True,
             single_large_frac: float = 0.25,
             per_chip_gb: Tuple[float, float] = (2.0, 6.0)) -> List[Job]:
    """The mixed single-chip / multi-chip open-arrival scenario: W-mix-style
    Rodinia jobs (``single_large_frac`` of them from the >4 GB families —
    large residents are what fragments a mesh) interleaved with seeded
    k-chip gangs, shuffled into one arrival order. ``probe_singles=False``
    swaps the compiler-probed singles for synthetic ones (same
    personalities, no XLA compiles) so smoke tests stay fast."""
    rng = np.random.default_rng(seed)
    jobs: List[Job] = []
    for i in range(n_singles):
        large = rng.random() < single_large_frac
        if probe_singles:
            jobs.append(make_rodinia_job(rng, large=large,
                                         name=f"single{i:03d}"))
        else:
            lo, hi = LARGE_RANGE if large else SMALL_RANGE
            vec = ResourceVector(
                hbm_bytes=int(rng.uniform(lo, hi)), flops=1e12,
                bytes_accessed=1e11,
                est_seconds=rng.uniform(*TARGET_JOB_SECONDS),
                core_demand=rng.uniform(0.2, 0.6),
                bw_demand=rng.uniform(0.2, 0.5))
            unit = UnitTask(fn=None, memobjs=frozenset({f"single{i}/ws"}),
                            resources=vec, name=f"single{i:03d}")
            jobs.append(Job(tasks=[Task(units=[unit], name=unit.name)],
                            name=unit.name))
    for i in range(n_gangs):
        chips = int(rng.choice(chip_choices))
        jobs.append(make_gang_job(rng, chips=chips,
                                  name=f"gang{i:03d}x{chips}",
                                  per_chip_gb=per_chip_gb))
    order = rng.permutation(len(jobs))
    return [jobs[i] for i in order]


# ---------------------------------------------------------------------------
# Overload workloads — the preemption subsystem's evaluation trace
# ---------------------------------------------------------------------------
# An OVERLOADED open-arrival scenario: long memory-heavy background jobs
# saturate the fleet, short urgent deadlined jobs arrive while they run, and
# small low-demand bystanders co-reside throughout. Memory is the binding
# constraint by construction (background + urgent footprints cannot share a
# 16 GB device), so an urgent arrival can only (a) wait out a background job
# many times its length, (b) be shed, or (c) preempt — the three systems
# benchmarks/bench_preempt.py compares. Bystanders are small enough to stay
# resident through the churn: their kernel slowdown is the "non-preempted
# degradation" the paper's <=2.5% envelope is checked against.

def _synthetic_job(rng: np.random.Generator, name: str, *,
                   gb: Tuple[float, float], seconds: Tuple[float, float],
                   core: float, bw: float, priority: int = 0) -> Job:
    vec = ResourceVector(
        hbm_bytes=int(rng.uniform(*gb) * GB), flops=1e12,
        bytes_accessed=1e11, est_seconds=float(rng.uniform(*seconds)),
        core_demand=core, bw_demand=bw)
    unit = UnitTask(fn=None, memobjs=frozenset({f"{name}/ws"}),
                    resources=vec, name=name)
    return Job(tasks=[Task(units=[unit], name=name)], name=name,
               priority=priority)


def overload_mix(seed: int, *, n_background: int = 8, n_bystander: int = 4,
                 n_urgent: int = 24, urgent_rate_hz: float = 1.2,
                 bg_gb: Tuple[float, float] = (9.5, 11.0),
                 bg_seconds: Tuple[float, float] = (16.0, 24.0),
                 urgent_gb: Tuple[float, float] = (8.5, 9.5),
                 urgent_seconds: Tuple[float, float] = (0.6, 1.4),
                 urgent_deadline_slack_s: float = 2.0,
                 urgent_priority: int = 5) -> List[Dict]:
    """Seeded overload trace as submission rows
    ``{"t", "job", "priority", "deadline_s", "kind"}`` sorted by arrival.

    Backgrounds (priority 0, no deadline, ~10 GB x ~20 s) and bystanders
    (~1 GB, low demand) arrive in the first two seconds and saturate the
    fleet; urgents (priority ``urgent_priority``, ~9 GB x ~1 s, deadline =
    est + slack) arrive Poisson at ``urgent_rate_hz`` from t=2 onwards.
    ``deadline_s`` is relative to the row's own ``t`` — callers pass it to
    ``Cluster.submit`` at that virtual time (or ignore it for the FIFO
    baseline and only measure against it)."""
    rng = np.random.default_rng(seed)
    rows: List[Dict] = []
    for i in range(n_background):
        rows.append({"t": float(rng.uniform(0.0, 1.0)),
                     "job": _synthetic_job(rng, f"bg{i:03d}", gb=bg_gb,
                                           seconds=bg_seconds,
                                           core=0.45, bw=0.30),
                     "priority": 0, "deadline_s": None, "kind": "background"})
    for i in range(n_bystander):
        rows.append({"t": float(rng.uniform(0.0, 2.0)),
                     "job": _synthetic_job(rng, f"by{i:03d}", gb=(0.8, 1.5),
                                           seconds=(8.0, 14.0),
                                           core=0.10, bw=0.08),
                     "priority": 0, "deadline_s": None, "kind": "bystander"})
    t = 2.0
    for i in range(n_urgent):
        t += float(rng.exponential(1.0 / urgent_rate_hz))
        job = _synthetic_job(rng, f"urgent{i:03d}", gb=urgent_gb,
                             seconds=urgent_seconds, core=0.50, bw=0.35,
                             priority=urgent_priority)
        rows.append({"t": t, "job": job, "priority": urgent_priority,
                     "deadline_s": job.total_seconds
                     + urgent_deadline_slack_s,
                     "kind": "urgent"})
    rows.sort(key=lambda r: r["t"])
    return rows


def drifting_mix(seed: int, *, n_jobs: int = 120, n_classes: int = 4,
                 rate_hz: float = 6.0, drift_start: float = 1.0,
                 drift_end: float = 2.5, mem_truth: float = 0.8,
                 est_range: Tuple[float, float] = (0.2, 0.8),
                 gb_range: Tuple[float, float] = (2.0, 6.0)) -> List[Dict]:
    """Seeded DRIFTING trace for the calibration plane (obs.calibrate):
    submission rows ``{"t", "job", "priority", "deadline_s", "kind"}``.

    ``n_classes`` resource classes each share ONE frozen predicted vector
    (so the calibration store's value-keyed class memos aggregate them),
    but every task carries a ``true_vec`` whose runtime is the prediction
    times a drift factor ramping linearly ``drift_start`` -> ``drift_end``
    over the trace — the probes grow steadily more wrong, the way a
    dataset-size or input-distribution shift degrades a stale estimate.
    Ground-truth memory is ``mem_truth`` x the predicted footprint
    (conservative probes), so inflate-only calibration yields ZERO memory
    violations — the acceptance-gate workload for bench_profile."""
    rng = np.random.default_rng(seed)
    classes = [ResourceVector(
        hbm_bytes=int(rng.uniform(*gb_range) * GB), flops=1e12,
        bytes_accessed=1e11, est_seconds=float(rng.uniform(*est_range)),
        core_demand=0.35, bw_demand=0.25) for _ in range(n_classes)]
    rows: List[Dict] = []
    t = 0.0
    for i in range(n_jobs):
        t += float(rng.exponential(1.0 / rate_hz))
        c = i % n_classes
        vec = classes[c]
        factor = drift_start + (drift_end - drift_start) \
            * (i / max(n_jobs - 1, 1))
        true_vec = dataclasses.replace(
            vec, est_seconds=vec.est_seconds * factor,
            hbm_bytes=int(vec.hbm_bytes * mem_truth))
        name = f"drift{i:03d}"
        unit = UnitTask(fn=None, memobjs=frozenset({f"{name}/ws"}),
                        resources=vec, name=name)
        job = Job(tasks=[Task(units=[unit], name=name, true_vec=true_vec)],
                  name=name)
        rows.append({"t": t, "job": job, "priority": 0,
                     "deadline_s": None, "kind": f"class{c}"})
    return rows


def split_gangs(jobs: Sequence[Job], *, dcn_bw: float = 12.5e9) -> List[Job]:
    """The chips-OBLIVIOUS view of a gang trace: every k-chip gang becomes k
    independent single-chip jobs, the way a flat scheduler sees today's
    sharded workloads. Scattered shards lose the contiguity guarantee, so
    their collectives cross slow inter-node paths: each shard's duration is
    re-roofed at ``collective_bytes / dcn_bw`` (vs the gang's intra-slice
    ICI time), and the logical job is only as fast as its LAST shard — the
    two effects ``bench_gang.py`` quantifies against gang-aware placement."""
    out: List[Job] = []
    for job in jobs:
        gangs = [t for t in job.tasks if t.resources.chips > 1]
        if not gangs:
            out.append(job)
            continue
        if len(job.tasks) > 1:
            # shattering a multi-task job into concurrent shard-jobs would
            # silently drop its sequential task ordering — refuse instead
            raise ValueError(
                f"split_gangs: job {job.name!r} has {len(job.tasks)} tasks; "
                "only single-task gang jobs have a faithful chips-oblivious "
                "split")
        for t in job.tasks:
            r = t.resources
            k = max(r.chips, 1)
            for j in range(k):
                shard_vec = dataclasses.replace(
                    r, hbm_bytes=r.hbm_bytes // k, chips=1,
                    flops=r.flops / k, bytes_accessed=r.bytes_accessed / k,
                    est_seconds=max(r.est_seconds,
                                    r.collective_bytes / dcn_bw))
                unit = UnitTask(fn=None,
                                memobjs=frozenset({f"{t.name}/shard{j}"}),
                                resources=shard_vec,
                                name=f"{t.name}/shard{j}")
                shard = Task(units=[unit], name=unit.name,
                             gang_id=t.gang_id or t.name)
                # the oblivious replay must keep the job's admission class
                out.append(Job(tasks=[shard], name=unit.name,
                               gang_id=t.gang_id or t.name,
                               priority=job.priority,
                               deadline_t=job.deadline_t))
    return out

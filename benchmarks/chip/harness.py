"""One run of one cell: the system under test built from the program's
public pieces, driven through a measured window, then checked.

The system is what a user of the program assembles (as ``chip_smoke.py``
does): weights, ``JaxModel``, a ``MGBAlg3Scheduler`` sized from the chip
(``device_capacity``), a live ``Cluster`` and a ``ServeEngine`` over it,
and training jobs handed to ``Cluster.submit``. The harness keeps its own
clock: every request is timed from its due time in the schedule, every
output token is stamped as the engine hands it out, and every training
step as it completes.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import arch  # noqa: E402
import traffic as TR  # noqa: E402
import weights as W  # noqa: E402

DRAIN_S = 60.0          # how long past the close the window's answers may come
# the engine's decode step as XLA names its module in a trace
DECODE_MODULE = "jit__decode"


@dataclasses.dataclass
class Served:
    """One request of the window and what the harness saw of it."""
    req: TR.Request
    due_t: float                    # absolute due time (host monotonic)
    submit_t: float = -1.0
    sr: Any = None                  # the engine's ServeRequest
    stamps: List[float] = dataclasses.field(default_factory=list)

    @property
    def prompt_len(self):
        return self.req.prompt_len

    @property
    def gen_len(self):
        return self.req.gen_len

    @property
    def done(self):
        return self.sr is not None and self.sr.status.value == "done"

    @property
    def resolved(self):
        return self.sr is not None and self.sr.status.value in (
            "done", "shed", "failed")


@dataclasses.dataclass
class TrainJob:
    index: int
    seed: int
    handle: Any = None
    step_end: List[float] = dataclasses.field(default_factory=list)
    losses: List[float] = dataclasses.field(default_factory=list)
    grad_norms: Optional[Dict[str, float]] = None     # after step 1
    update_norms: Optional[Dict[str, float]] = None   # after step 3
    error: str = ""


def program_config(cfg: dict):
    """The program's ArchConfig for a configuration file: its registered
    architecture with the file's sizes, and the fields the architecture's
    file sets (``program_fields``). A value that differs from the
    registered one must be listed in ``reduced``."""
    from repro.configs.registry import get_arch
    base = get_arch(cfg["program_arch"])
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab")
    def value(k):       # the registered value, a head size resolved
        return base.resolved_head_dim if k == "head_dim" and base.n_heads \
            else getattr(base, k)
    fields = {k: cfg[k] for k in keys if k in cfg}
    fields.update(arch.module(cfg).program_fields(cfg, base))
    kw = {k: v for k, v in fields.items() if v != value(k)}
    for k, v in kw.items():
        if k not in cfg.get("reduced", []):
            raise ValueError(f"{cfg['name']}: {k}={v} differs from the "
                             f"program's {value(k)} and is not in "
                             "'reduced'")
    return dataclasses.replace(base, **kw)


def check_layout(cfg: dict, pc) -> None:
    """The benchmark's weight layout must be the program's parameter tree."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import init_params
    ours = W.abstract_params(cfg)
    theirs = jax.eval_shape(
        lambda k: init_params(pc, k, param_dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    a = jax.tree_util.tree_flatten_with_path(ours)[0]
    b = jax.tree_util.tree_flatten_with_path(theirs)[0]
    sa = [(jax.tree_util.keystr(p), x.shape, x.dtype) for p, x in a]
    sb = [(jax.tree_util.keystr(p), x.shape, x.dtype) for p, x in b]
    if sa != sb:
        diff = [(x, y) for x, y in zip(sa, sb) if x != y][:3]
        raise ValueError(f"weight layout differs from the program's: {diff} "
                         f"({len(sa)} vs {len(sb)} leaves)")


def train_config(cfg: dict, tr: dict) -> dict:
    """The training jobs' configuration: full width, ``layers`` deep."""
    out = dict(cfg, n_layers=tr["layers"])
    out["reduced"] = sorted(set(cfg.get("reduced", [])) | {"n_layers"})
    return out


class Cell:
    """Build, warm, run, measure and free one cell's system."""

    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float,
                 trace: bool):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.seconds, self.trace = float(seconds), trace
        self.served: List[Served] = []
        self.pumps: List[tuple] = []     # (t0, t1, rows, kv_positions)
        self.jobs: List[TrainJob] = []
        self.compiles: List[float] = []
        self.trace_data = None
        self.t_open = self.t_close = 0.0
        self.trace_t = (0.0, 0.0)
        self.mem: Dict[str, int] = {}
        self.marks: List[tuple] = []     # (phase of set-up, wall time at end)

    def _mark(self, phase: str):
        self.marks.append((phase, time.time()))

    # -- set-up -------------------------------------------------------------
    def build(self):
        import jax
        from repro.core.cluster import Cluster
        from repro.core.executor import device_capacity
        from repro.core.scheduler import MGBAlg3Scheduler
        from repro.serve.engine import SLO, JaxModel, ServeEngine

        self._mark("import")
        self.pc = program_config(self.cfg)
        check_layout(self.cfg, self.pc)
        self._mark("layout")
        self.device = jax.devices()[0]
        params = W.make_params(self.cfg, self.seed, self.device)
        jax.block_until_ready(params)
        self._mark("weights")
        mix = self.mix
        self.model = JaxModel(self.pc, params, max_batch=mix["rows"],
                              max_seq=mix["max_seq"], attn_impl="flash_jnp")
        del params
        self._mark("model")
        n, hbm = device_capacity()
        self.sched = MGBAlg3Scheduler(n, hbm_per_device=hbm)
        # two workers: a prefill and a training job can run side by side
        self.cluster = Cluster(self.sched, workers=2)
        # no request is shed or ranked by a deadline: FIFO within a class
        self.engine = ServeEngine(self.cluster, self.model,
                                  max_batch=mix["rows"],
                                  slo=SLO(ttft_s=3600.0, tpot_s=3600.0))
        self._mark("engine")
        if "train" in mix:
            self._build_train()
            self._mark("train")

    def _build_train(self):
        import jax
        import jax.numpy as jnp
        from repro.core.probe import probe_fn
        from repro.optim import adamw
        from repro.train.train_step import make_train_step
        tr = self.mix["train"]
        self.tcfg = train_config(self.cfg, tr)
        tpc = program_config(self.tcfg)
        check_layout(self.tcfg, tpc)
        o = tr["optimizer"]
        self.opt_cfg = adamw.AdamWConfig(
            lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
            weight_decay=o["weight_decay"], clip_norm=o["clip_norm"],
            warmup_steps=o["warmup_steps"], total_steps=tr["steps"],
            moment_dtype=tpc.optimizer_moment_dtype)
        self.train_step = jax.jit(make_train_step(tpc, self.opt_cfg),
                                  donate_argnums=(0, 1))
        self.init_opt = jax.jit(lambda p: adamw.init_state(self.opt_cfg, p))
        self.leaf_norms = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
            t))
        maker = W._maker(W._items(self.tcfg))

        def delta_norms(p, key):
            p0 = maker(key)
            return jax.tree_util.tree_map(
                lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                    a.astype(jnp.float32) - b.astype(jnp.float32)))), p, p0)
        self.delta_norms = jax.jit(delta_norms)
        p_sds = W.abstract_params(self.tcfg)
        o_sds = jax.eval_shape(self.init_opt, p_sds)
        b_sds = {k: jax.ShapeDtypeStruct((tr["batch"], tr["seq"]), jnp.int32)
                 for k in ("tokens", "labels")}
        self.train_vec = probe_fn(self.train_step, p_sds, o_sds, b_sds)

    def train_batches(self, job_seed: int):
        """The job's feed: ``steps`` batches of distinct rows, each row a
        sequence of ``seq + 1`` token ids split into inputs and next-token
        labels."""
        tr = self.mix["train"]
        rng = np.random.default_rng([int(job_seed) % (1 << 63), 3])
        toks = rng.integers(0, self.cfg["vocab"],
                            (tr["steps"], tr["batch"], tr["seq"] + 1),
                            dtype=np.int32)
        return [(t[:, :-1], t[:, 1:]) for t in toks]

    def _train_runner(self, job: TrainJob, check: bool):
        import jax

        def runner(device):
            try:
                params = W.make_params(self.tcfg, job.seed, device)
                opt = self.init_opt(params)
                for i, (x, y) in enumerate(self.train_batches(job.seed)):
                    batch = {"tokens": jax.device_put(x, device),
                             "labels": jax.device_put(y, device)}
                    params, opt, m = self.train_step(params, opt, batch)
                    job.losses.append(float(m["loss"]))
                    job.step_end.append(time.monotonic())
                    if check and i == 0:
                        job.grad_norms = _host(self.leaf_norms(opt["mu"]))
                    if check and i == 2:
                        key = jax.device_put(jax.random.PRNGKey(
                            W.jax_seed(job.seed, 1)), device)
                        job.update_norms = _host(self.delta_norms(params, key))
            except Exception as e:  # the job's failure is the run's result
                job.error = f"{type(e).__name__}: {e}"
                raise
        return runner

    def submit_train(self, index: int, check: bool) -> TrainJob:
        from repro.core.executor import ExecJob
        from repro.core.task import Job, Task, UnitTask
        tr = self.mix["train"]
        job = TrainJob(index, W.jax_seed(self.seed, 1000 + index))
        name = f"train/{index}"
        unit = UnitTask(fn=None, memobjs=frozenset({name}),
                        resources=self.train_vec, name=name)
        j = Job(tasks=[Task(units=[unit], name=name)], name=name)
        job.handle = self.cluster.submit(
            ExecJob(job=j, runners=[self._train_runner(job, check)]),
            priority=tr["priority"])
        self.jobs.append(job)
        return job

    def warm(self):
        """Run every shape the window uses once: a request per prompt
        length (prefill probe and program, argmax, cache insert, the decode
        step at the loop's rows) and, beside serving, one training job."""
        rng = np.random.default_rng(0)
        for plen in TR.prompt_lengths(self.mix):
            self.engine.submit(prompt=rng.integers(
                0, self.cfg["vocab"], (1, plen), dtype=np.int32), gen_len=3)
        self.engine.drain(timeout_s=1200.0)
        self._mark("warm serve")
        if "train" in self.mix:
            job = self.submit_train(-1, check=True)
            job.handle.result(timeout=1200.0)
            if job.error:
                raise RuntimeError(f"warm-up training job: {job.error}")
            self.jobs.clear()
        self.engine.requests.clear()
        self.engine.join_log.clear()

    # -- the window ---------------------------------------------------------
    def _submit(self, s: Served, now: float):
        import jax
        s.submit_t = now
        with jax.profiler.TraceAnnotation("bench.submit"):
            s.sr = self.engine.submit(prompt=s.req.prompt[None],
                                      gen_len=s.req.gen_len)
        self.served.append(s)

    def _stamp(self, live: List[Served], t0: float, t1: float):
        rows = kv = 0
        still = []
        for s in live:
            sr = s.sr
            n = sr.n_tokens
            if n >= 1 and not s.stamps and sr.t_first >= 0:
                s.stamps.append(sr.t_first)
            while len(s.stamps) < n:
                s.stamps.append(t1)
                rows += 1
                kv += s.req.prompt_len + len(s.stamps) - 1
            if not s.resolved:
                still.append(s)
        if rows:
            self.pumps.append((t0, t1, rows, kv))
        return still

    def run_window(self):
        import jax
        mix = self.mix
        reqs = TR.schedule(mix, self.seed, self.seconds, self.cfg["vocab"])
        listener = _CompileCounter(self.compiles)
        tracer = _Profile(self) if self.trace else None
        if tracer is not None:
            tracer.open()
        self.t_open = time.monotonic()
        self.t_close = self.t_open + self.seconds
        live: List[Served] = []
        pending = [Served(r, self.t_open + r.due) for r in reqs]
        nxt = 0
        if "train" in mix:
            for i in range(mix["train"]["concurrency"]):
                self.submit_train(i, check=(i == 0))
        listener.on()
        while True:
            now = time.monotonic()
            while nxt < len(pending) and pending[nxt].due_t <= now:
                self._submit(pending[nxt], now)
                live.append(pending[nxt])
                nxt += 1
            if now >= self.t_close:
                break
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.pump"):
                emitted = self.engine.pump()
            t1 = time.monotonic()
            live = self._stamp(live, t0, t1)
            if "train" in mix:
                self._renew_train()
            if not emitted:
                wait = 0.001
                if nxt < len(pending):
                    wait = min(wait, max(pending[nxt].due_t - t1, 0.0))
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(wait)
        listener.off()
        if tracer is not None:
            tracer.close()
        # the window's answers: every request sent in it, and the training
        # jobs running at its close, may finish up to DRAIN_S later
        limit = self.t_close + DRAIN_S
        while live and time.monotonic() < limit:
            t0 = time.monotonic()
            emitted = self.engine.pump()
            live = self._stamp(live, t0, time.monotonic())
            if not emitted:
                time.sleep(0.001)
        for job in self.jobs:
            try:
                job.handle.result(timeout=max(limit - time.monotonic(), 1.0))
            except Exception as e:  # noqa: BLE001 - recorded as a failure
                job.error = job.error or f"{type(e).__name__}: {e}"
        self._read_memory()
        if tracer is not None:
            tracer.stop()

    def _renew_train(self):
        tr = self.mix["train"]
        running = [j for j in self.jobs if not _terminal(j.handle)]
        for _ in range(tr["concurrency"] - len(running)):
            self.submit_train(len(self.jobs), check=False)

    def _read_memory(self):
        import jax
        peak = limit = 0
        for d in jax.devices()[:len(self.sched.devices)]:
            st = d.memory_stats() or {}
            peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
            limit = max(limit, int(st.get("bytes_limit", 0)))
        self.mem = {"peak_bytes_in_use": peak, "bytes_limit": limit,
                    "reserved_peak": max(d.peak_hbm for d in self.sched.devices)}

    # -- after the window ---------------------------------------------------
    def free(self):
        """Release the program's state, so the reference has the chip."""
        self.engine.shutdown()
        self.cluster.drain()
        self.cluster.shutdown()
        for s in self.served:
            sr = s.sr
            s.sr = _Done(sr.status.value, list(sr.tokens), sr.error,
                         sr.t_first, sr.n_tokens)
        for attr in ("engine", "model", "cluster", "sched", "train_step",
                     "init_opt", "leaf_norms", "delta_norms"):
            if hasattr(self, attr):
                delattr(self, attr)
        for j in self.jobs:
            j.handle = _DoneHandle(j.handle)
        release()


def release():
    """Give the chip back: every device buffer, and every compiled program
    (a loaded TPU program keeps its scratch memory reserved). Closures the
    program keeps (runners, callbacks) can hold weights and caches past
    their owners, and its probe caches compiled programs for good."""
    import jax
    from repro.core import probe
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    probe._probe_cache.clear()
    jax.clear_caches()


@dataclasses.dataclass
class _Done:
    """What remains of a ServeRequest once the engine is gone."""
    status_value: str
    tokens: List[int]
    error: str
    t_first: float
    n_tokens: int

    @property
    def status(self):
        return _Status(self.status_value)


@dataclasses.dataclass
class _Status:
    value: str


class _DoneHandle:
    """What remains of a JobHandle once the cluster is gone."""

    def __init__(self, h):
        self.status = _Status(h.status.value)
        self.records = list(h.records)
        self.error = h.job.error


def _terminal(h) -> bool:
    return h.status.value in ("done", "crashed", "cancelled", "shed")


def _host(tree) -> Dict[str, float]:
    import jax
    return {jax.tree_util.keystr(p): float(x)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


class _CompileCounter:
    """Counts XLA compilations (backend compiles and persistent-cache loads
    alike) between ``on`` and ``off``."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, sink: List[float]):
        import jax
        self.sink, self.active = sink, False

        def listen(event, duration, **_):
            if self.active and event == self.EVENT:
                sink.append(duration)
        jax.monitoring.register_event_duration_secs_listener(listen)

    def on(self):
        self.active = True

    def off(self):
        self.active = False


class _Profile:
    """The profiler over the window: started before it opens, so that
    starting it stalls no submission, and stopped after the drain, so that
    writing and reading the trace delays no answer."""

    def __init__(self, cell: Cell):
        import jax
        self.cell = cell
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self.dir)
        self.annot = jax.profiler.TraceAnnotation("bench.traced")

    def open(self):
        self.annot.__enter__()
        self.t0 = time.monotonic()

    def close(self):
        """The window closed: end the span the reduction reads."""
        self.annot.__exit__(None, None, None)
        self.cell.trace_t = (self.t0, time.monotonic())

    def stop(self):
        """After the drain: stop the profiler and read its trace."""
        import jax
        import trace as TRC
        jax.profiler.stop_trace()
        try:
            self.cell.trace_data = TRC.load(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def percentile(xs: List[float], p: float) -> float:
    """Nearest-rank percentile (``p`` in 0..100); inf counts as a miss."""
    xs = sorted(xs)
    if not xs:
        return math.nan
    k = max(int(math.ceil(p / 100.0 * len(xs))) - 1, 0)
    return xs[k]

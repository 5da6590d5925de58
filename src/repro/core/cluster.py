"""Open-arrival submission front-end: the ``Cluster`` object jobs arrive at.

The paper's scheduler is a daemon — probes submit tasks whenever a process
reaches a launch point, not as a pre-declared batch. ``Cluster`` is that
front door for this repo: ``submit`` may be called at ANY time (including
while earlier jobs are mid-flight) and returns a future-like ``JobHandle``;
``drain`` is the barrier; ``shutdown`` tears the engine down.

    cluster = Cluster(MGBAlg3Scheduler(4), workers=4)
    h = cluster.submit(ej, priority=5, deadline_s=2.0)
    ...                        # keep submitting while it runs
    recs = h.result(timeout=30)    # per-task ExecRecords
    cluster.drain()

Two interchangeable backends sit behind the same API:

  * ``backend="live"`` — the event-driven ``Executor``: real jitted JAX
    computations, wall-clock time, a bounded execution pool;
  * ``backend="sim"``  — the discrete-event ``Simulator``: virtual clock,
    processor-sharing interference model, no real execution. ``step()``
    advances the clock so submissions can interleave with simulated
    progress.

Both route admission through the scheduler's OWN priority/deadline waiter
queue, so the same submission trace produces the same admission order live
and simulated — the property that makes simulator studies predictive of the
serving path.

Priority/deadline semantics (enforced in the scheduler's admission queue,
not by this caller): higher ``priority`` admits first; within a priority
class, earliest ``deadline_s`` first (EDF — by default a deadline is an
ordering hint, not an enforcement: late tasks still run); no-deadline tasks
rank after deadlined peers of their class; arrival order breaks remaining
ties, and a task evicted by a device failure restarts at the front of its
class. With ``shed_late=True`` the deadline becomes (soft) enforcement: a
job still PARKED when its deadline passes is failed with ``JobStatus.SHED``
at the next admission drain instead of admitted late.
"""
from __future__ import annotations

import enum
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Union

from repro.core.executor import ExecJob, ExecRecord, Executor, _JobRun
from repro.core.scheduler.base import Scheduler
from repro.core.scheduler.preempt import PreemptionMixin
from repro.core.simulator import Simulator, _JobState
from repro.core.task import Job
from repro.obs import explain as obsx
from repro.obs.calibrate import CalibrationStore, attach_calibrator
from repro.obs.events import Tracer, attach_tracer
from repro.obs.explain import Explainer, attach_explainer
from repro.obs.export import write_chrome_trace
from repro.obs.metrics import MetricsRegistry
from repro.obs.profile import Profiler, TaskProfile
from repro.obs.replay import FlightRecorder


class JobStatus(enum.Enum):
    QUEUED = "queued"        # submitted, not yet executing
    RUNNING = "running"      # at least one task started
    DONE = "done"            # all tasks completed
    CRASHED = "crashed"      # OOM / runner exception / never feasible
    CANCELLED = "cancelled"  # ended by JobHandle.cancel()
    SHED = "shed"            # parked past its deadline, failed at a drain
    #                          (only with shed_late=True deadline shedding)


class JobHandle:
    """Future-like view of one submitted job, valid on either backend.

    ``result(timeout)`` blocks (live: wall clock; sim: advances the virtual
    clock) until the job resolves and returns its per-task ``ExecRecord``
    list; check ``status`` to distinguish DONE from CRASHED/CANCELLED.
    """

    def __init__(self, cluster: "Cluster", job: Job,
                 state: Union[_JobRun, _JobState]):
        self._cluster = cluster
        self.job = job
        self._state = state

    # -- lifecycle ----------------------------------------------------------
    @property
    def status(self) -> JobStatus:
        s = self._state
        finished = s.done.is_set() if isinstance(s, _JobRun) else s.done
        if finished:
            if s.cancelled:
                return JobStatus.CANCELLED
            if s.shed:
                return JobStatus.SHED
            if self.job.crashed:
                return JobStatus.CRASHED
            return JobStatus.DONE
        return JobStatus.RUNNING if s.started else JobStatus.QUEUED

    @property
    def records(self) -> List[ExecRecord]:
        """Per-task execution records accumulated so far (live wall times or
        virtual-clock times, matching the backend)."""
        return list(self._state.records)

    def result(self, timeout: Optional[float] = None) -> List[ExecRecord]:
        """Wait until the job resolves; returns its ``ExecRecord`` list.
        Live backend: blocks up to ``timeout`` wall seconds (raises
        ``TimeoutError`` on expiry). Sim backend: advances the virtual clock
        until the job resolves (``timeout`` bounds virtual seconds)."""
        s = self._state
        if isinstance(s, _JobRun):
            if not s.done.wait(timeout):
                raise TimeoutError(f"job {self.job.name!r} still "
                                   f"{self.status.value} after {timeout}s")
        else:
            sim = self._cluster._sim
            limit = sim.now + timeout if timeout is not None else None
            while not s.done:
                if limit is not None and sim.now > limit:
                    raise TimeoutError(f"job {self.job.name!r} still "
                                       f"{self.status.value} at virtual "
                                       f"t={sim.now:.3f}")
                if not sim.step():
                    break  # simulation idle: job crashed-at-drain or stuck
            if not s.done:
                raise TimeoutError(
                    f"job {self.job.name!r} cannot make progress")
        return self.records

    def cancel(self) -> bool:
        """Cancel the job: a parked/queued job ends immediately (its waiter
        leaves the scheduler's admission queue with no state leaked); a
        running task finishes its current kernel first. Returns False iff
        the job had already finished; True otherwise — the job then reports
        CANCELLED (or CRASHED if its in-flight kernel crashes)."""
        return self._cluster._cancel(self._state)

    def explain(self) -> Dict[str, List]:
        """Per-task decision verdicts: why is this job still parked, who
        evicted it and at what cost, where did it land. Delegates to
        ``Cluster.explain`` (needs the cluster built with ``explain=`` or
        ``trace=``)."""
        return self._cluster.explain(self)

    def profile(self) -> Dict[str, TaskProfile]:
        """Per-task observed-vs-predicted attribution: runtime error against
        the probe estimate, memory reserved vs high-water, the parked /
        dispatch / execution delay decomposition. Delegates to
        ``Cluster.profile`` (needs the cluster built with ``trace=``)."""
        return self._cluster.profile(self)


class Cluster:
    """The open-arrival submission surface over a scheduler + backend."""

    def __init__(self, scheduler: Scheduler, *, workers: Optional[int] = None,
                 backend: str = "live",
                 devices: Optional[Sequence[object]] = None,
                 poll_interval: float = 0.05, crash_delay: float = 8.0,
                 shed_late: bool = False, preempt: Optional[bool] = None,
                 trace: Union[None, bool, Tracer] = None,
                 explain: Union[None, bool, Explainer] = None,
                 calibrate: Union[None, bool, CalibrationStore] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 flight_path: Optional[str] = None):
        self.sched = scheduler
        self.backend = backend
        # deadline enforcement (the shedding half): a parked waiter whose
        # deadline already passed is failed with JobStatus.SHED at the next
        # admission drain instead of being admitted late. Off by default —
        # deadlines stay a pure EDF ordering hint unless the operator opts in
        scheduler.shed_expired = shed_late
        # deadline/priority enforcement (the eviction half): preempt=True
        # lets an arriving waiter that strictly outranks a resident evict it
        # (checkpoint-based, work-conserving — see scheduler.preempt); the
        # scheduler must be preemption-capable. preempt=False disables it on
        # a capable scheduler; None (default) keeps the scheduler's own
        # setting (preemptive classes enable themselves at construction).
        if preempt is not None:
            if preempt and not isinstance(scheduler, PreemptionMixin):
                raise ValueError(
                    f"preempt=True needs a preemption-capable scheduler, "
                    f"got {type(scheduler).__name__} — use "
                    f"PreemptiveAlg2Scheduler / PreemptiveAlg3Scheduler / "
                    f"PreemptiveGangScheduler from repro.core.scheduler")
            scheduler.preempt_enabled = bool(preempt)
        n_workers = workers if workers is not None \
            else len(scheduler.devices)
        self._ex: Optional[Executor] = None
        self._sim: Optional[Simulator] = None
        if backend == "live":
            # a scheduler previously driven by a Simulator has its _clock
            # bound to that sim's (now frozen) virtual time: restore wall
            # monotonic so deadline shedding judges live deadlines correctly
            scheduler._clock = time.monotonic
            self._ex = Executor(scheduler, workers=n_workers,
                                devices=devices)
        elif backend == "sim":
            self._sim = Simulator(scheduler, workers=n_workers,
                                  poll_interval=poll_interval,
                                  crash_delay=crash_delay)
        else:
            raise ValueError(f"unknown backend {backend!r} "
                             "(expected 'live' or 'sim')")
        # event-sourced telemetry (repro.obs): trace=True builds a default
        # Tracer, or pass a pre-sized one. Attached AFTER backend
        # construction — attach_tracer binds the tracer's clock to the
        # scheduler's _clock late, so it follows the sim's virtual-clock
        # repointing (and the live backend's wall-monotonic restore) above
        self.trace: Optional[Tracer] = None
        self.flight: Optional[FlightRecorder] = None
        self.metrics: Optional[MetricsRegistry] = metrics
        # NB: identity checks, not truthiness — Tracer/Explainer define
        # __len__, so a freshly-built (empty) instance is falsy and a bare
        # `if trace:` would silently skip attaching it
        want_trace = trace is not None and trace is not False
        if want_trace:
            self.trace = trace if isinstance(trace, Tracer) else Tracer()
            attach_tracer(scheduler, self.trace)
            if flight_path is not None:
                self.flight = FlightRecorder(self.trace, flight_path,
                                             registry=metrics)
        # decision explainability (repro.obs.explain): explain=True builds
        # a default Explainer, or pass a pre-sized one; explain=None follows
        # trace — a traced cluster answers "why" as well as "what". Attached
        # after the backend for the same late clock binding as the tracer.
        self.explainer: Optional[Explainer] = None
        if explain is None:
            explain = want_trace
        if explain is not False:
            self.explainer = explain if isinstance(explain, Explainer) \
                else Explainer()
            attach_explainer(scheduler, self.explainer)
        # online probe calibration (repro.obs.calibrate): calibrate=True
        # builds a default CalibrationStore, or pass a tuned one. Admission
        # then uses EWMA-corrected est_seconds and safety-margin memory;
        # completions feed the store. A scheduler pre-wrapped in
        # CalibratedScheduler is discovered instead of double-attached.
        self.calibration: Optional[CalibrationStore] = None
        if calibrate is not None and calibrate is not False:
            self.calibration = calibrate \
                if isinstance(calibrate, CalibrationStore) \
                else CalibrationStore()
            attach_calibrator(scheduler, self.calibration)
        else:
            self.calibration = getattr(scheduler, "_calib", None)
        self.handles: List[JobHandle] = []
        # scheduler counters are lifetime totals; snapshot them so a cluster
        # built over a reused scheduler reports only its own activity
        self._attempts0 = getattr(scheduler, "begin_attempts", 0)
        self._preempt0 = getattr(scheduler, "preemptions", 0)
        self._migr0 = getattr(scheduler, "migrations", 0)
        self._submit_lock = threading.Lock()
        # aggregate-stats counters, maintained at submit time and by each
        # job's resolution callback (the backend fires it exactly once per
        # job) so stats() is O(1) instead of re-scanning every handle —
        # polling it at 1e5 submitted jobs must not stall the control plane
        self._stats_lock = threading.Lock()
        self._n_jobs = 0
        self._t0 = float("inf")    # earliest arrival over ALL jobs
        self._t1 = float("-inf")   # latest finish over RESOLVED jobs
        self._n_done = 0
        self._n_crashed = 0
        self._n_cancelled = 0
        self._n_shed = 0
        self._turnaround_sum = 0.0  # over DONE jobs only

    # -- submission ----------------------------------------------------------
    def submit(self, job: Union[Job, ExecJob], *,
               runners: Optional[List[Callable]] = None,
               priority: Optional[int] = None,
               deadline_s: Optional[float] = None,
               on_done: Optional[Callable[["JobHandle"], None]] = None
               ) -> JobHandle:
        """Submit ``job`` NOW — at any time, including while earlier jobs are
        executing. ``priority`` (higher first) and ``deadline_s`` (seconds
        from submission; EDF within a priority class) rank the job in the
        scheduler's admission queue; leaving either None keeps any stamp
        already on the Job (default class 0, no deadline). Live backend
        wants an ``ExecJob`` (or a ``Job`` plus ``runners``); the sim
        backend takes a plain ``Job``. Returns a ``JobHandle``
        immediately.

        ``on_done(handle)`` (optional) fires exactly once when the job
        resolves (DONE/CRASHED/CANCELLED/SHED) — the streaming-completion
        hook serve.engine chains prefill→decode-slot joins on. Live backend:
        fires on a backend thread; keep it non-blocking. It may fire before
        ``submit`` returns (an instantly-resolving job)."""
        done_cb = self._on_job_resolved if on_done is None \
            else self._chain_on_done(on_done)
        with self._submit_lock:
            if self._ex is not None:
                ej = self._as_execjob(job, runners)
                deadline_t = (time.monotonic() + deadline_s
                              if deadline_s is not None else None)
                state: Union[_JobRun, _JobState] = self._ex.submit(
                    ej, priority=priority, deadline_t=deadline_t,
                    on_done=done_cb)
                handle = JobHandle(self, ej.job, state)
            else:
                plain = job.job if isinstance(job, ExecJob) else job
                deadline_t = (self._sim.now + deadline_s
                              if deadline_s is not None else None)
                state = self._sim.submit(plain, priority=priority,
                                         deadline_t=deadline_t,
                                         on_done=done_cb)
                handle = JobHandle(self, plain, state)
            with self._stats_lock:
                self._n_jobs += 1
                self._t0 = min(self._t0, handle.job.arrival_t)
            self.handles.append(handle)
            return handle

    def _chain_on_done(self, user_cb: Callable[["JobHandle"], None]
                       ) -> Callable[[Union[_JobRun, _JobState]], None]:
        """Wrap a user completion callback around the stats-folding backend
        callback. The backend may resolve an (e.g. empty) job INSIDE
        ``submit``, before the public handle exists — so the handle is built
        on demand from the backend state rather than captured."""
        def cb(state: Union[_JobRun, _JobState]) -> None:
            self._on_job_resolved(state)
            job = state.ej.job if isinstance(state, _JobRun) else state.job
            user_cb(JobHandle(self, job, state))
        return cb

    def _on_job_resolved(self, state: Union[_JobRun, _JobState]) -> None:
        """Backend resolution callback (fired exactly once per job): fold the
        job's terminal status into the maintained aggregate counters. The
        classification mirrors ``JobHandle.status`` — cancel beats shed
        beats crash beats done."""
        job = state.ej.job if isinstance(state, _JobRun) else state.job
        with self._stats_lock:
            if job.finish_t >= 0:
                self._t1 = max(self._t1, job.finish_t)
            if state.cancelled:
                self._n_cancelled += 1
            elif state.shed:
                self._n_shed += 1
            elif job.crashed:
                self._n_crashed += 1
            else:
                self._n_done += 1
                self._turnaround_sum += job.finish_t - job.arrival_t
        if self.flight is not None and job.crashed \
                and not state.cancelled and not state.shed:
            self.flight.dump("crash")

    @staticmethod
    def _as_execjob(job: Union[Job, ExecJob],
                    runners: Optional[List[Callable]]) -> ExecJob:
        if isinstance(job, ExecJob):
            return job
        if runners is None:
            # placement/ordering studies on the live engine: tasks place,
            # execute instantly, release
            runners = [(lambda device: None)] * len(job.tasks)
        if len(runners) != len(job.tasks):
            raise ValueError(f"{len(runners)} runners for "
                             f"{len(job.tasks)} tasks")
        return ExecJob(job=job, runners=list(runners))

    def _cancel(self, state: Union[_JobRun, _JobState]) -> bool:
        if isinstance(state, _JobRun):
            return self._ex.cancel(state)
        return self._sim.cancel(state)

    # -- barriers / clock ----------------------------------------------------
    def drain(self) -> None:
        """Barrier: block (live) or advance the virtual clock (sim) until
        every job submitted so far has resolved. New submissions remain legal
        afterwards — drain is a checkpoint, not a shutdown. A sim drain that
        hits its virtual time limit with work still pending raises instead
        of returning quietly: a capped run must not read as a completed one."""
        if self._ex is not None:
            self._ex.drain()
        else:
            self._sim_drain_checked()
        if self.flight is not None:
            self.flight.dump("drain", always=True)

    def _sim_drain_checked(self) -> None:
        res = self._sim.drain()
        if res.truncated:
            raise RuntimeError(
                f"simulation drain truncated at virtual t={self._sim.now:.0f}s "
                f"with work still pending ({res.completed} completed) — the "
                f"time limit was hit, not the end of the trace")

    def step(self) -> bool:
        """Sim backend: advance the virtual clock one event (False when
        idle). Live backend: no-op False — wall time advances on its own."""
        if self._sim is not None:
            return self._sim.step()
        return False

    def run_until(self, t: float) -> None:
        """Sim backend: advance the virtual clock to exactly ``t`` (the
        open-arrival driver — submit, run_until the next arrival, submit).
        Live backend: no-op; wall time advances on its own."""
        if self._sim is not None:
            self._sim.run_until(t)

    def inject_failure(self, device) -> None:
        """Declare ``device`` dead NOW on either backend (sim: residents'
        virtual runs stop and re-park; live: the scheduler's mark_dead
        path). ``obs.whatif`` replays recorded fleet faults through this."""
        if self._sim is not None:
            self._sim.inject_failure(device)
        else:
            self.sched.mark_dead(device)

    def revive(self, device) -> None:
        """Bring ``device`` back in service on either backend."""
        if self._sim is not None:
            self._sim.revive_device(device)
        else:
            self.sched.revive(device)

    def jax_device(self, index: int):
        """The jax device behind scheduler device ``index`` on the live
        backend (where a runner placed there computes); None on the sim
        backend, which computes nothing."""
        return self._ex.device_map[index] if self._ex is not None else None

    @property
    def now(self) -> float:
        """Current time on the backend's clock (virtual for sim)."""
        return self._sim.now if self._sim is not None else time.monotonic()

    def shutdown(self) -> None:
        """Drain, then stop the live execution pool (sim: just drains).
        The cluster is reusable — the next ``submit`` restarts the pool."""
        if self._ex is not None:
            self._ex.shutdown()
        else:
            self._sim_drain_checked()

    def explain(self, handle: "JobHandle") -> Dict[str, List[obsx.Verdict]]:
        """Why is this job still parked / who evicted it, at what cost —
        answered in one call, per task name: the recorded verdict window
        (rejections with per-device reasons, skips, preemption plans,
        evictions naming the preemptor, the final placement) plus, for a
        task parked RIGHT NOW, a live rejection probe of the current
        queue state — so even a waiter the drain never individually
        probed (class-memo skip) reports at least one structured reason
        per attempted device. Requires ``explain=`` (on by default when
        the cluster is traced)."""
        if self.explainer is None:
            raise RuntimeError(
                "Cluster was built without explain= — pass explain=True "
                "(or an Explainer) to record decision verdicts")
        ex = self.explainer
        eq = getattr(self.sched, "explain_queue", None)
        out: Dict[str, List[obsx.Verdict]] = {}
        for task in handle.job.tasks:
            verdicts = ex.verdicts(task.uid)
            if eq is not None:
                live = eq(task)
                if live is not None:       # parked right now: probe live
                    verdicts.append(obsx.Verdict(
                        seq=-1, t=self.now, uid=task.uid, name=task.name,
                        action=obsx.REJECTED, reasons=tuple(live),
                        data={"live": True}))
            out[task.name or str(task.uid)] = verdicts
        return out

    def profile(self, handle: Optional["JobHandle"] = None):
        """Observed-vs-predicted attribution from the event stream (requires
        ``trace=``). With a handle: per-task ``TaskProfile`` records for that
        job, keyed by task name — runtime error vs the probe estimate,
        memory reserved vs observed high-water, parked/dispatch/execution
        delay decomposition, evictions. Without: the fleet summary —
        aggregate error stats, per-device occupancy, and (when the cluster
        is calibrated) the calibration store's accuracy report. Mirrors
        ``explain()``/``JobHandle.explain()``."""
        if self.trace is None:
            raise RuntimeError("Cluster was built without trace= — pass "
                               "trace=True (or a Tracer) to enable profiling")
        prof = Profiler(self.trace, self.calibration)
        if handle is None:
            return prof.summary()
        profs = prof.profiles()
        out: Dict[str, TaskProfile] = {}
        for task in handle.job.tasks:
            p = profs.get(task.uid)
            if p is None:          # never reached an emission site yet
                p = TaskProfile(task.uid)
                p.name = task.name
            out[task.name or str(task.uid)] = p
        return out

    def export_trace(self, path: str, *,
                     profile_counters: Optional[bool] = None) -> Dict:
        """Write the tracer's event window as a Chrome/Perfetto trace-event
        JSON (chrome://tracing or https://ui.perfetto.dev) and return the
        document. Requires the cluster to have been built with ``trace=``.

        On a sharded or multi-pod control plane the device tracks are
        named ``pod{p}/dev{d}`` (pod factoring derived from the
        scheduler) instead of flat ``device {i}``.

        ``profile_counters`` adds the profiling plane's counter tracks
        (per-device occupancy %, fleet prediction-error %); default: on
        exactly when the cluster is calibrated."""
        if self.trace is None:
            raise RuntimeError("Cluster was built without trace= — pass "
                               "trace=True (or a Tracer) to enable telemetry")
        if profile_counters is None:
            profile_counters = self.calibration is not None
        return write_chrome_trace(self.trace.events(), path,
                                  devices_per_pod=self._devices_per_pod(),
                                  profile_counters=profile_counters)

    def _devices_per_pod(self) -> Optional[int]:
        """Pod factoring for trace-track / dashboard labels: a sharded
        wrapper's uniform shard width, or a multi-pod gang topology's
        pod size; None for flat fleets (keeps ``device {i}`` labels)."""
        sched = self.sched
        dpp = getattr(sched, "_shard_devs", None)
        if dpp and len(getattr(sched, "shards", ())) > 1:
            return dpp
        topo = getattr(sched, "topo", None)
        if topo is not None and getattr(topo, "pods", 1) > 1:
            return topo.rows * topo.cols
        return None

    def __enter__(self) -> "Cluster":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # -- metrics -------------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        """Aggregate metrics over every job submitted so far, with the same
        keys ``Executor.run`` reports (plus ``cancelled``). Times are wall
        seconds (live) or virtual seconds (sim).

        O(1): read from counters maintained at submit time and by each
        job's resolution callback — never a scan over the handle list, so
        a dashboard may poll this at 1e5 submitted jobs without stalling
        submission. Unresolved jobs count toward nothing but the arrival
        front ``t0`` (exactly as the historical handle scan had it)."""
        preemptions = getattr(self.sched, "preemptions", 0) - self._preempt0
        migrations = getattr(self.sched, "migrations", 0) - self._migr0
        with self._stats_lock:
            if not self._n_jobs:
                return {"makespan_s": 0.0, "throughput_jobs_per_s": 0.0,
                        "completed": 0, "crashed": 0,
                        "mean_turnaround_s": 0.0, "sched_attempts": 0,
                        "cancelled": 0, "shed": 0,
                        "preemptions": preemptions,
                        "migrations": migrations}
            t0 = self._t0
            t1 = self._t1 if self._t1 > float("-inf") else t0
            makespan = max(t1 - t0, 1e-9)
            n_done = self._n_done
            return {
                "makespan_s": makespan,
                "throughput_jobs_per_s": n_done / makespan,
                "completed": n_done,
                "crashed": self._n_crashed,
                "cancelled": self._n_cancelled,
                "shed": self._n_shed,
                "preemptions": preemptions,
                "migrations": migrations,
                "mean_turnaround_s":
                    self._turnaround_sum / max(n_done, 1),
                "sched_attempts":
                    getattr(self.sched, "begin_attempts", 0)
                    - self._attempts0,
            }

"""Live executor: an event-driven engine running REAL jitted JAX computations
under a scheduler — the end-to-end path probe -> admit/enqueue -> wakeup ->
lazy bind -> launch -> release (paper §IV prototype, minus MPS which has no
TPU analogue).

Engine shape (the paper's daemon, in-process):

  * **open arrival**: ``submit(ej)`` may be called at ANY time — including
    while earlier jobs are mid-flight — exactly like probes arriving at the
    paper's daemon. ``run(jobs)`` survives as the closed-batch compatibility
    shim (submit everything, drain, report);
  * a single **dispatcher** owns the pending work: each job submits its next
    task via ``Scheduler.admit_or_enqueue`` — a blocked task holds NO thread,
    it sits in the scheduler's priority/deadline admission queue;
  * every ``task_end`` re-drives admission (the paper's *notify*), and the
    admission callback pushes the (task, placement) pair onto a **bounded
    execution pool** sized to the device count, not the job count. A gang
    placement (``GangReservation`` from the gang scheduler) dispatches the
    task as ONE bound group: its runner receives the ordered device list of
    the whole reservation;
  * completion callbacks advance the owning job to its next task (or finish
    it), so thousands of queued jobs need only ``workers`` threads;
  * ``drain()`` is the barrier (wait until every submitted job resolved),
    ``shutdown()`` tears the pool down. ``repro.core.cluster.Cluster`` is the
    user-facing front-end over this engine.

``PollingExecutor`` preserves the previous worker-pool protocol — one thread
per in-flight job spinning ``task_begin`` in a sleep(poll) loop — as the
baseline ``benchmarks/bench_executor.py`` measures the event-driven engine
against.

Device table (``device_table``): on an accelerator every scheduler device
IS one attached chip — the scheduler may claim no more devices than are
attached and no more HBM per device than the chip's
``memory_stats()["bytes_limit"]``, or admission would promise memory the
chip does not have; each runner receives its placed ``jax.Device`` (or the
gang's device list) and must put its arrays there. On the CPU backend only
(tests, rehearsals) any number of scheduler devices map round-robin onto the
attached CPU devices: placement, memory accounting and OOM/crash semantics
stay per *virtual* device while the arithmetic runs wherever jax puts it.
"""
from __future__ import annotations

import dataclasses
import queue as queue_mod
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

from repro.core import lazy
from repro.core.scheduler.base import DEADLINE_SHED, DEFAULT_HBM, Scheduler
from repro.core.task import Job, Task
from repro.core.topology import placement_devices
from repro.obs import events as obs
from repro.obs.spans import span


class OOMError(RuntimeError):
    """Raised when an admitted task exceeds its device's memory (CG path)."""


# ``ExecRecord.t_start`` sentinel: the task crashed BEFORE its kernel ever
# launched (infeasible-at-submit, fleet-shrank-while-parked, pre-dispatch
# OOM). Distinct from any real timestamp so latency consumers can exclude
# never-started records instead of folding a fake zero-length execution
# window into their means — check ``rec.started``, not ``rec.crashed``.
NEVER_STARTED = -1.0


@dataclasses.dataclass
class ExecRecord:
    job: str
    task: str
    device: int          # lead device of the placement (-1 = never placed)
    t_queue: float
    t_start: float       # NEVER_STARTED if the task crashed pre-launch
    t_end: float
    crashed: bool = False
    # size of the reserved device group (1 for single-chip tasks); the gang
    # bench groups queueing-delay percentiles by this
    gang_chips: int = 1
    # when the scheduler's admission callback handed the task to the
    # execution pool (-1: never admitted): t_admit - t_queue is the wait in
    # the scheduler, t_start - t_admit the wait for a pool worker
    t_admit: float = -1.0

    @property
    def started(self) -> bool:
        """True iff the task's kernel actually began executing — only then
        do t_start/t_end bound a real execution window."""
        return self.t_start >= 0.0


@dataclasses.dataclass
class ExecJob:
    """A live job: ordered (task, runner) pairs. ``runner(device)`` executes
    the task's computation after the lazy buffers are bound to ``device``."""
    job: Job
    runners: List[Callable[[object], None]]
    buffers: Dict[str, lazy.LazyBuffer] = dataclasses.field(default_factory=dict)
    # cooperative preemption surface (set/observed only under a preemptive
    # scheduler): ``preempted`` is SET when the scheduler evicts this job's
    # in-flight task and CLEARED at each (re)dispatch — a cooperative runner
    # polls it between steps and returns early, since the eviction already
    # released the reservation and the epoch fence voids this attempt's
    # completion. ``on_preempt`` (optional) fires once per eviction with the
    # evicted Task: wire it to train/checkpoint.py's save for training tasks
    # so the resumed dispatch — possibly on a DIFFERENT device, which is how
    # migration falls out of requeue + placement — restores from the last
    # committed step instead of recomputing.
    preempted: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    on_preempt: Optional[Callable[[Task], None]] = None


def device_capacity(devices: Optional[Sequence[object]] = None
                    ) -> Tuple[int, int]:
    """(device count, per-device HBM bytes) of the attached devices — what a
    scheduler for this process should be built with. The capacity is the
    smallest ``bytes_limit`` the devices report; the CPU backend reports
    none and gets ``DEFAULT_HBM`` per (virtual) device."""
    devs = list(devices) if devices is not None else list(jax.devices())
    if devs[0].platform == "cpu":
        return len(devs), DEFAULT_HBM
    return len(devs), min(_bytes_limit(d) for d in devs)


def _bytes_limit(dev) -> int:
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if limit is None:
        raise ValueError(f"{dev} reports no bytes_limit: its usable HBM "
                         "cannot be verified")
    return int(limit)


def device_table(scheduler: Scheduler,
                 devices: Sequence[object]) -> List[object]:
    """Scheduler device index -> jax device (see the module docstring):
    one-to-one and capacity-checked on an accelerator, round-robin on the
    CPU backend only."""
    n = len(scheduler.devices)
    real = list(devices)
    if real[0].platform == "cpu":
        return [real[i % len(real)] for i in range(n)]
    if n > len(real):
        raise ValueError(
            f"scheduler has {n} devices but only {len(real)} "
            f"{real[0].platform} device(s) are attached")
    for dev, jd in zip(scheduler.devices, real):
        limit = _bytes_limit(jd)
        if dev.total_hbm > limit:
            raise ValueError(
                f"scheduler device {dev.index} claims {dev.total_hbm} B of "
                f"HBM but {jd} has bytes_limit {limit} B")
    return real[:n]


def _empty_stats() -> Dict[str, float]:
    return {"makespan_s": 0.0, "throughput_jobs_per_s": 0.0,
            "completed": 0, "crashed": 0, "mean_turnaround_s": 0.0,
            "sched_attempts": 0}


@dataclasses.dataclass
class _JobRun:
    """Dispatcher-side job state: which task is next, when it was queued,
    plus the open-arrival lifecycle bits ``JobHandle`` observes."""
    ej: ExecJob
    next_task: int = 0
    t_queue: float = 0.0
    started: bool = False
    cancel_requested: bool = False
    cancelled: bool = False
    shed: bool = False      # parked past its deadline and shed at a drain
    on_done: Optional[Callable[["_JobRun"], None]] = None
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    records: List[ExecRecord] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Ready:
    """An admitted task waiting for an execution-pool thread. ``placement``
    is a device index (flat schedulers) or a ``GangReservation`` (gang
    scheduler — the task's unit group runs bound to the whole device set)."""
    jr: _JobRun
    task_idx: int
    placement: object
    epoch: int
    t_admit: float


class Executor:
    """Event-driven executor: open-arrival submission, admission wakeups,
    bounded execution pool."""

    def __init__(self, scheduler: Scheduler, *, workers: int,
                 devices: Optional[Sequence[object]] = None,
                 poll_interval: float = 0.002):
        self.sched = scheduler
        self.workers = workers
        self.poll = poll_interval  # kept for API compat (PollingExecutor uses it)
        self.device_map = device_table(
            scheduler, devices if devices is not None else jax.devices())
        self.records: List[ExecRecord] = []
        self._rec_lock = threading.Lock()
        # preemptive scheduler: observe evictions so the victim's running
        # attempt is signalled to stop cooperatively (and its checkpoint
        # callback fires) — the re-admission callback then re-dispatches it
        self._jr_by_uid: Dict[int, "_JobRun"] = {}
        # per-task attempt serialization: a re-dispatched incarnation must
        # not run concurrently with a still-executing superseded attempt —
        # they share ExecJob.buffers and the single `preempted` event, so
        # attempt 2 waits for attempt 1's runner to exit (an evicted
        # cooperative runner exits promptly; a non-cooperative one finishes
        # its kernel, exactly the cost it would pay anyway)
        self._attempt_locks: Dict[int, threading.Lock] = {}
        # uid -> epoch of the attempt currently armed on ExecJob.preempted,
        # guarded by _signal_lock: an eviction notice is addressed to its
        # victim's superseded epoch, and delivery may lag (the delivering
        # thread holds no lock) — a notice older than the armed attempt must
        # be dropped, or it would stop the FRESH attempt and turn its early
        # return into a current-epoch completion (silent lost work)
        self._armed_epoch: Dict[int, int] = {}
        self._signal_lock = threading.Lock()
        if hasattr(scheduler, "add_preempt_listener"):
            scheduler.add_preempt_listener(self._on_preempt)
        # open-arrival engine state
        self._ready: Optional["queue_mod.Queue[Optional[_Ready]]"] = None
        self._threads: List[threading.Thread] = []
        self._running = False
        self._lifecycle = threading.Lock()     # guards start/shutdown
        self._state = threading.Condition()    # guards _inflight
        self._inflight = 0

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        """Spin up the execution pool; idempotent (``submit`` auto-starts)."""
        with self._lifecycle:
            self._start_locked()

    def _start_locked(self) -> None:
        if self._running:
            return
        self._ready = queue_mod.Queue()
        self._threads = [threading.Thread(target=self._pool_worker,
                                          daemon=True)
                         for _ in range(self.workers)]
        for t in self._threads:
            t.start()
        self._running = True

    def drain(self) -> None:
        """Barrier: block until every job submitted so far has resolved
        (done, crashed, or cancelled). Jobs submitted while draining extend
        the wait — the barrier is over the in-flight count, not a snapshot."""
        with self._state:
            while self._inflight:
                self._state.wait()

    def shutdown(self) -> None:
        """Drain, then stop the pool threads. ``submit`` restarts it. A
        ``submit`` racing shutdown either lands before the teardown (the
        re-drain below picks it up) or blocks on the lifecycle lock and
        restarts a fresh pool — never lost."""
        while True:
            self.drain()
            with self._lifecycle:
                if not self._running:
                    return
                with self._state:
                    if self._inflight:
                        continue  # a submit raced the drain: wait again
                for _ in self._threads:
                    self._ready.put(None)
                for t in self._threads:
                    t.join()
                self._threads = []
                self._running = False
                return

    # -- open-arrival API ----------------------------------------------------
    def submit(self, ej: ExecJob, *, priority: Optional[int] = None,
               deadline_t: Optional[float] = None,
               on_done: Optional[Callable[[_JobRun], None]] = None
               ) -> _JobRun:
        """Enter ``ej`` into the admission path NOW — legal at any time,
        including while earlier jobs are mid-flight. ``priority`` /
        ``deadline_t`` stamp every task of the job (None keeps stamps already
        on the job); the scheduler's admission queue enforces the ordering.
        Returns the job's ``_JobRun`` (wrap it in a ``cluster.JobHandle`` for
        the user-facing future API)."""
        job = ej.job
        if priority is not None:
            job.priority = priority
        if deadline_t is not None:
            job.deadline_t = deadline_t
        for t in job.tasks:
            t.priority = job.priority
            t.deadline_t = job.deadline_t
            if t.gang_id is None:
                t.gang_id = job.gang_id
        jr = _JobRun(ej, on_done=on_done)
        job.arrival_t = time.monotonic()
        with self._lifecycle:
            # pool-start + in-flight increment are atomic w.r.t. shutdown's
            # teardown check, so a racing submit is never stranded
            self._start_locked()
            with self._state:
                self._inflight += 1
        if not job.tasks:
            # empty job: nothing to place — finish immediately with a zeroed
            # record instead of indexing runners[0]
            now = time.monotonic()
            self._record(jr, ExecRecord(job.name, "", -1, now, now, now))
            self._finish(jr, crashed=False)
        else:
            self._submit_next(jr)
        return jr

    def cancel(self, jr: _JobRun) -> bool:
        """Cancel: a parked waiter is removed from the admission queue
        immediately (no scheduler state leaks); a running task finishes its
        current kernel, then the job stops advancing. Returns False iff the
        job had already finished (too late); True otherwise — the job then
        ends CANCELLED (or CRASHED, if its in-flight kernel crashes). The
        flag is raised under the finish lock, so a True return can never be
        contradicted by a DONE status."""
        with self._state:
            if jr.done.is_set():
                return jr.cancelled
            jr.cancel_requested = True
        idx = jr.next_task
        tasks = jr.ej.job.tasks
        if idx < len(tasks) and self.sched.cancel_wait(tasks[idx]):
            # it was parked: the admission callback can never fire now
            self._finish(jr, crashed=False, cancelled=True)
        # else admitted or mid-handoff: the execute/completion/finish path
        # sees the flag
        return True

    # -- compatibility shim ---------------------------------------------------
    def run(self, jobs: Sequence[ExecJob]) -> Dict[str, float]:
        """Closed-batch protocol: submit every job, drain, report. Kept as a
        thin shim over the open-arrival engine (metrics keys unchanged)."""
        if not jobs:
            return _empty_stats()
        attempts0 = getattr(self.sched, "begin_attempts", 0)
        self.start()
        # deterministic arrival order: jobs enter the admission path in the
        # order given, so queue-rank wakeups replay the submission sequence
        for ej in jobs:
            self.submit(ej)
        self.drain()
        self.shutdown()
        return self._stats(jobs, attempts0)

    # -- engine internals -----------------------------------------------------
    def _record(self, jr: _JobRun, rec: ExecRecord) -> None:
        with self._rec_lock:
            self.records.append(rec)
            jr.records.append(rec)

    def _finish(self, jr: _JobRun, *, crashed: bool,
                cancelled: bool = False, shed: bool = False) -> None:
        for t in jr.ej.job.tasks:
            self._jr_by_uid.pop(t.uid, None)
            self._attempt_locks.pop(t.uid, None)
            self._armed_epoch.pop(t.uid, None)
        with self._state:
            if jr.done.is_set():
                return  # double-finish guard (cancel raced a completion)
            # a cancel requested before this point wins over DONE (matching
            # the sim backend, where the completion path checks the flag
            # even on the job's last task); a crash stays a crash
            if jr.cancel_requested and not crashed:
                cancelled = True
            jr.ej.job.crashed = jr.ej.job.crashed or crashed
            jr.cancelled = cancelled
            jr.shed = shed and not cancelled
            jr.ej.job.finish_t = time.monotonic()
            jr.done.set()
            self._inflight -= 1
            if self._inflight == 0:
                self._state.notify_all()
        lazy.free_all(jr.ej.buffers)
        if jr.on_done is not None:
            jr.on_done(jr)

    def _on_preempt(self, victims) -> None:
        """Eviction notice from the scheduler: signal the running attempt to
        stop cooperatively and take the job's checkpoint. Each notice names
        the victim's SUPERSEDED epoch; if a fresh attempt has already armed
        itself with a newer epoch (late delivery — the delivering thread
        holds no lock), the notice is dropped: stopping the fresh attempt
        would count its early return as a real completion. The superseded
        attempt's eventual ``task_end`` is epoch-fenced either way."""
        for t, epoch in victims:
            jr = self._jr_by_uid.get(t.uid)
            if jr is None:
                continue
            with self._signal_lock:
                stale = self._armed_epoch.get(t.uid, -1) > epoch
                if not stale:
                    jr.ej.preempted.set()
            if not stale and jr.ej.on_preempt is not None:
                try:
                    jr.ej.on_preempt(t)
                except Exception:
                    # a failing checkpoint must not poison the scheduler's
                    # notify path; the task simply restarts from its last
                    # committed state
                    pass

    def _submit_next(self, jr: _JobRun) -> None:
        if jr.cancel_requested:
            self._finish(jr, crashed=False, cancelled=True)
            return
        idx = jr.next_task
        task = jr.ej.job.tasks[idx]
        self._jr_by_uid[task.uid] = jr
        jr.t_queue = time.monotonic()
        # read at emit time (attach_tracer may run after construction);
        # this path is per-task, not per-admission — not hot
        tr = getattr(self.sched, "_trace", None)
        if tr is not None:
            tr.emit(obs.SUBMIT, task.uid, task.name,
                    data=obs.submit_data(task, jr.ej.job.name,
                                         jr.ej.job.uid))
        if not self.sched.can_ever_fit(task):
            # never feasible on any alive device (or, for a gang, no
            # feasible device-group shape): crash-at-submit with the
            # scheduler's explanation instead of waiting forever
            jr.ej.job.error = self.sched.infeasible_reason(task)
            if tr is not None:
                tr.emit(obs.CRASH, task.uid, task.name,
                        data={"reason": "infeasible"})
            self._record(jr, ExecRecord(
                jr.ej.job.name, task.name, -1, jr.t_queue, NEVER_STARTED,
                time.monotonic(), crashed=True))
            self._finish(jr, crashed=True)
            return

        def on_admit(t: Task, placement, epoch: int,
                     jr=jr, idx=idx) -> None:
            # fires under task_end/notify of *another* task (or inline on
            # immediate admission): just hand off to the execution pool.
            # placement None = the fleet shrank to where this task can never
            # run (mark_dead sweep): crash the job instead of waiting;
            # DEADLINE_SHED = the scheduler shed the parked waiter past its
            # deadline: fail the job with SHED status, not CRASHED
            if placement is DEADLINE_SHED:
                # no record: the job consumed no device time (matches the
                # sim backend — a shed handle reports records == [])
                self._finish(jr, crashed=False, shed=True)
                return
            if placement is None:
                jr.ej.job.error = self.sched.infeasible_reason(t)
                self._record(jr, ExecRecord(
                    jr.ej.job.name, t.name, -1, jr.t_queue, NEVER_STARTED,
                    time.monotonic(), crashed=True))
                self._finish(jr, crashed=True)
                return
            self._ready.put(_Ready(jr, idx, placement, epoch,
                                   time.monotonic()))

        self.sched.admit_or_enqueue(task, on_admit)

    def _execute(self, item: _Ready) -> None:
        jr, task = item.jr, item.jr.ej.job.tasks[item.task_idx]
        # a gang placement binds the task to its WHOLE reserved device
        # group; the lead device carries the record/audit identity
        devs = placement_devices(item.placement)
        lead = devs[0]
        # evicted while queued for the pool (device died): the re-admitted
        # incarnation owns this task now — drop the stale work item
        if self.sched.admission_epoch(task) != item.epoch:
            return
        tr = getattr(self.sched, "_trace", None)
        if tr is not None:
            tr.emit(obs.DISPATCH, task.uid, task.name, lead, item.epoch,
                    data={"chips": len(devs)})
        if jr.cancel_requested:
            # cancelled between admission and execution: release the
            # admission (it holds the whole reservation) and end the job
            if self.sched.task_end(task, epoch=item.epoch):
                self._finish(jr, crashed=False, cancelled=True)
            return
        # memory-unsafe scheduler may have oversubscribed: OOM crash if ANY
        # member device of the group is past capacity (memory safety must
        # hold across every device a job touches)
        if any(self.sched.devices[d].oom() for d in devs):
            if not self.sched.task_end(task, epoch=item.epoch):
                return  # fenced: evicted + re-admitted elsewhere mid-check
            if tr is not None:
                # after task_end's END: the resources WERE released before
                # the crash was recorded (the tolerated DONE->DEAD arc)
                tr.emit(obs.CRASH, task.uid, task.name, lead, item.epoch,
                        data={"reason": "oom"})
            self._record(jr, ExecRecord(
                jr.ej.job.name, task.name, lead, jr.t_queue, NEVER_STARTED,
                time.monotonic(), crashed=True, gang_chips=len(devs),
                t_admit=item.t_admit))
            self._finish(jr, crashed=True)
            return
        # serialize with any still-running superseded attempt of this task,
        # then arm the cooperative-preemption surface: clear FIRST, then
        # re-check the epoch. An eviction racing this dispatch lands on one
        # side or the other: before the re-check, its epoch bump voids this
        # attempt (the eaten event cannot be meant for a running attempt —
        # the lock guarantees none is); after it, the notice finds the
        # cleared event and stops the runner below.
        if task.uid not in self._jr_by_uid:
            return  # job already resolved: stale straggler dispatch
        lock = self._attempt_locks.setdefault(task.uid, threading.Lock())
        crashed = False
        error = ""
        t_start = None
        with lock:
            with self._signal_lock:
                # clear + arm atomically w.r.t. notice delivery: from here a
                # notice is delivered only if addressed to THIS epoch (or a
                # later one, which cannot exist yet)
                jr.ej.preempted.clear()
                self._armed_epoch[task.uid] = item.epoch
            if self.sched.admission_epoch(task) == item.epoch:
                # the execution window starts only once any superseded
                # attempt has exited — its tail must not be charged to
                # this attempt's record
                t_start = time.monotonic()
                # stamped for the calibration store: task_end reads start_t
                # to attribute wall-clock runtime against the probe estimate
                task.start_t = t_start
                jr.started = True
                if tr is not None:
                    tr.emit(obs.BEGIN, task.uid, task.name, lead,
                            item.epoch)
                try:
                    # lazy runtime: replay buffer queues on the gang's lead
                    # device, then launch the task's unit group as ONE bound
                    # dispatch — a single-chip runner receives its device, a
                    # gang runner receives the ordered device list of its
                    # reservation
                    lazy.kernel_launch_prepare(jr.ej.buffers,
                                               self.device_map[lead])
                    bound = (self.device_map[lead] if len(devs) == 1
                             else [self.device_map[d] for d in devs])
                    with span("repro.exec.run", task=task.name):
                        jr.ej.runners[item.task_idx](bound)
                except Exception as e:
                    # keep the cause: a compile error or a real
                    # RESOURCE_EXHAUSTED must not read as a bare crash count
                    crashed = True
                    error = f"{task.name}: {type(e).__name__}: {e}"
        if t_start is None:
            # superseded between pool pickup and dispatch. If the fresh
            # incarnation meanwhile finished the whole job, _finish's
            # cleanup may have raced our setdefault — reap the entries it
            # can no longer see
            if jr.done.is_set():
                self._attempt_locks.pop(task.uid, None)
                self._armed_epoch.pop(task.uid, None)
            return
        # epoch fence: if the device died mid-run the task was evicted and
        # re-enqueued — this completion is stale, the fresh incarnation
        # owns the job's progress (and the resources were already freed)
        current = self.sched.task_end(task, epoch=item.epoch)
        if not current:
            return
        if crashed:
            jr.ej.job.error = error
            if tr is not None:
                tr.emit(obs.CRASH, task.uid, task.name, lead, item.epoch,
                        data={"reason": "runner", "error": error})
            now = time.monotonic()
            self._record(jr, ExecRecord(
                jr.ej.job.name, task.name, lead, jr.t_queue,
                t_start, now, crashed=True, gang_chips=len(devs),
                t_admit=item.t_admit))
            self._finish(jr, crashed=True)
            return
        self._record(jr, ExecRecord(
            jr.ej.job.name, task.name, lead, jr.t_queue, t_start,
            time.monotonic(), gang_chips=len(devs), t_admit=item.t_admit))
        jr.next_task += 1
        if jr.next_task >= len(jr.ej.job.tasks):
            self._finish(jr, crashed=False)
        else:
            self._submit_next(jr)

    def _pool_worker(self) -> None:
        while True:
            item = self._ready.get()
            if item is None:
                return
            self._execute(item)

    def _stats(self, jobs: Sequence[ExecJob], attempts0: int
               ) -> Dict[str, float]:
        done = [j.job for j in jobs if not j.job.crashed]
        t0 = min(j.job.arrival_t for j in jobs)
        t1 = max(j.job.finish_t for j in jobs)
        makespan = max(t1 - t0, 1e-9)
        return {
            "makespan_s": makespan,
            "throughput_jobs_per_s": len(done) / makespan,
            "completed": len(done),
            "crashed": sum(1 for j in jobs if j.job.crashed),
            "mean_turnaround_s": sum(
                j.job.finish_t - j.job.arrival_t for j in jobs
                if not j.job.crashed) / max(len(done), 1),
            "sched_attempts":
                getattr(self.sched, "begin_attempts", 0) - attempts0,
        }


class PollingExecutor(Executor):
    """The previous protocol: one worker thread per in-flight job, each
    spinning ``task_begin`` in a sleep(poll) retry loop. Kept as the baseline
    the event-driven engine is benchmarked against — concurrency is capped at
    ``workers`` and blocked jobs burn a thread + poll attempts each."""

    def run(self, jobs: Sequence[ExecJob]) -> Dict[str, float]:
        if not jobs:
            return _empty_stats()
        attempts0 = getattr(self.sched, "begin_attempts", 0)
        q: "queue_mod.Queue[ExecJob]" = queue_mod.Queue()
        for j in jobs:
            j.job.arrival_t = time.monotonic()
            q.put(j)

        def worker(_wid: int) -> None:
            while True:
                try:
                    ej = q.get_nowait()
                except queue_mod.Empty:
                    return
                try:
                    self._run_job(ej)
                except OOMError:
                    ej.job.crashed = True
                finally:
                    lazy.free_all(ej.buffers)  # crash paths must free too
                ej.job.finish_t = time.monotonic()

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(self.workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return self._stats(jobs, attempts0)

    def _run_job(self, ej: ExecJob) -> None:
        for task, runner in zip(ej.job.tasks, ej.runners):
            t_queue = time.monotonic()
            # probe -> scheduler (task_begin), retry while infeasible
            placement = self.sched.task_begin(task)
            while placement is None:
                if not self.sched.can_ever_fit(task):
                    raise OOMError(f"{task.name}: never feasible")
                time.sleep(self.poll)
                placement = self.sched.task_begin(task)
            devs = placement_devices(placement)
            lead = devs[0]
            # memory-unsafe scheduler may have oversubscribed: OOM crash
            if any(self.sched.devices[d].oom() for d in devs):
                self.sched.task_end(task)
                with self._rec_lock:
                    self.records.append(ExecRecord(
                        ej.job.name, task.name, lead, t_queue,
                        NEVER_STARTED, time.monotonic(), crashed=True,
                        gang_chips=len(devs)))
                raise OOMError(
                    f"{task.name}: {task.resources.hbm_bytes} B exceeded "
                    f"device {lead} capacity")
            t_start = time.monotonic()
            try:
                lazy.kernel_launch_prepare(ej.buffers, self.device_map[lead])
                bound = (self.device_map[lead] if len(devs) == 1
                         else [self.device_map[d] for d in devs])
                runner(bound)
            finally:
                self.sched.task_end(task)
            with self._rec_lock:
                self.records.append(ExecRecord(
                    ej.job.name, task.name, lead, t_queue, t_start,
                    time.monotonic(), gang_chips=len(devs)))

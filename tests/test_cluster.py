"""Concurrency + ordering battery for the open-arrival ``Cluster`` API
(ISSUE 3 tentpole): streaming submission over both backends.

  * ``submit`` is legal while earlier jobs are mid-flight, on the live
    executor AND the virtual-clock simulator;
  * ``JobHandle.cancel()`` of a parked waiter removes it from the scheduler's
    admission queue without leaking ``_admit_cbs``/epoch state;
  * priority inversion: a high-priority job submitted late overtakes parked
    low-priority waiters — enforced by the waiter queue itself;
  * EDF: within one priority class, earliest absolute deadline first;
  * ``drain()`` vs late ``submit()`` race: nothing is lost, nothing hangs;
  * live and sim backends produce the SAME admission order for the same
    submission trace;
  * empty-``tasks`` jobs finish immediately with a zeroed record;
  * property tests: stable FIFO within a priority class, eviction-restart
    jumps to the front of its class (not above higher classes).
"""
import threading
import time

import pytest
from _hypothesis_fallback import given, settings, st

from repro.core.cluster import Cluster, JobStatus
from repro.core.executor import ExecJob, Executor
from repro.core.scheduler import MGBAlg2Scheduler, MGBAlg3Scheduler
from repro.core.simulator import Simulator
from repro.core.task import Job, ResourceVector, Task, UnitTask
from repro.obs.replay import admission_order, first_divergence

GB = 1024**3


def mk_task(name, mem_gb=2.0, demand=0.5, est=0.005):
    vec = ResourceVector(hbm_bytes=int(mem_gb * GB), flops=1e9,
                         bytes_accessed=1e9, est_seconds=est,
                         core_demand=demand, bw_demand=demand)
    return Task(units=[UnitTask(fn=None, memobjs=frozenset({name}),
                                resources=vec, name=name)], name=name)


def mk_job(name, mem_gb=2.0, demand=0.5, est=0.005, n_tasks=1):
    tasks = [mk_task(f"{name}.{k}" if n_tasks > 1 else name, mem_gb, demand,
                     est) for k in range(n_tasks)]
    return Job(tasks=tasks, name=name)


def live_ej(name, mem_gb=2.0, demand=0.5, sleep=0.003, body=None):
    job = mk_job(name, mem_gb, demand)
    runner = body if body is not None else (
        lambda device, s=sleep: time.sleep(s))
    return ExecJob(job=job, runners=[runner])


# ---------------------------------------------------------------------------
# open arrival: submit while prior jobs are executing
# ---------------------------------------------------------------------------

def test_live_submit_while_running():
    """Acceptance criterion: new jobs enter while earlier ones are mid-
    flight — no pre-declared batch."""
    started = threading.Event()

    def slow(device):
        started.set()
        time.sleep(0.05)

    c = Cluster(MGBAlg3Scheduler(2), workers=2)
    h1 = c.submit(live_ej("a", body=slow))
    assert started.wait(5.0)
    assert h1.status is JobStatus.RUNNING
    h2 = c.submit(live_ej("b", sleep=0.001))   # mid-flight submission
    assert h2.result(timeout=5.0)[0].task == "b"
    c.drain()
    assert h1.status is JobStatus.DONE and h2.status is JobStatus.DONE
    c.shutdown()


def test_sim_submit_while_running():
    """Same property on the virtual clock: a job submitted at t>0 while an
    earlier job is mid-flight is admitted at the current virtual time."""
    c = Cluster(MGBAlg3Scheduler(2), workers=4, backend="sim")
    h1 = c.submit(mk_job("a", est=5.0, n_tasks=2))
    assert c.step()                      # completes a.0 at t=5; a.1 starts
    assert h1.status is JobStatus.RUNNING
    assert 0.0 < c.now < 10.0
    h2 = c.submit(mk_job("b", est=1.0))  # arrives mid-flight of job a
    assert h2.job.arrival_t == c.now
    c.drain()
    assert h1.status is JobStatus.DONE and h2.status is JobStatus.DONE
    assert h2.records[0].t_start >= h2.job.arrival_t


def test_sim_result_advances_virtual_clock():
    c = Cluster(MGBAlg2Scheduler(1), workers=2, backend="sim")
    h1 = c.submit(mk_job("a", demand=1.0, est=3.0))
    h2 = c.submit(mk_job("b", demand=1.0, est=3.0))
    recs = h2.result()                  # drives the clock until b resolves
    assert h2.status is JobStatus.DONE
    assert recs[0].t_start >= 3.0 - 1e-9   # b waited for exclusive a


# ---------------------------------------------------------------------------
# cancellation
# ---------------------------------------------------------------------------

def test_cancel_parked_waiter_leaves_no_scheduler_state():
    """cancel() of a parked waiter: admission queue entry, _admit_cbs and
    _epochs all cleaned (the satellite leak check)."""
    release = threading.Event()
    c = Cluster(MGBAlg3Scheduler(1), workers=2)
    hog = c.submit(live_ej("hog", mem_gb=10.0,
                           body=lambda d: release.wait(5.0)))
    w = c.submit(live_ej("w", mem_gb=10.0))
    deadline = time.monotonic() + 5.0
    while c.sched.waiting_count() == 0 and time.monotonic() < deadline:
        time.sleep(0.001)               # wait until w is parked
    assert w.status is JobStatus.QUEUED
    assert w.cancel() is True
    assert w.status is JobStatus.CANCELLED
    uid = w.job.tasks[0].uid
    assert c.sched.waiting_count() == 0
    assert uid not in c.sched._admit_cbs and uid not in c.sched._epochs
    release.set()
    c.drain()
    assert hog.status is JobStatus.DONE
    assert c.stats()["cancelled"] == 1 and c.stats()["completed"] == 1
    # cancelled waiter never executed
    assert w.records == []
    c.shutdown()


def test_cancel_running_job_stops_after_current_task():
    seen = []
    c = Cluster(MGBAlg3Scheduler(1), workers=1)
    job = mk_job("j", n_tasks=3)
    h = c.submit(ExecJob(job=job, runners=[
        lambda d: (seen.append(0), time.sleep(0.05)),
        lambda d: seen.append(1),
        lambda d: seen.append(2)]))
    deadline = time.monotonic() + 5.0
    while not seen and time.monotonic() < deadline:
        time.sleep(0.001)
    h.cancel()
    c.drain()
    assert h.status is JobStatus.CANCELLED
    assert seen in ([0], [0, 1])      # never ran the full job
    # current task's resources were released on cancel
    assert all(d.used_hbm == 0 and d.used_slots == 0
               for d in c.sched.devices)
    c.shutdown()


def test_cancel_of_evicted_restart_keeps_epoch_fence():
    """Cancelling a parked eviction-restart waiter must NOT delete its
    bumped epoch: the superseded run may still be mid-kernel, and its late
    task_end(epoch=old) has to stay fenced."""
    sched = MGBAlg3Scheduler(2)
    fired = []
    cb = lambda t, dev, epoch: fired.append((dev, epoch))
    t = mk_task("t", mem_gb=9.0)
    assert sched.admit_or_enqueue(t, cb)             # admitted, epoch 0
    assert sched.task_begin(mk_task("hog", mem_gb=9.0)) is not None
    sched.mark_dead(t.device)                        # evict: epoch -> 1,
    assert sched.waiting_count() == 1                # re-parked (hog full)
    assert sched.cancel_wait(t) is True
    # the old incarnation's completion arrives late: still a fenced no-op
    assert sched.task_end(t, epoch=0) is False


def test_sim_cancel_parked_waiter():
    c = Cluster(MGBAlg3Scheduler(1), workers=4, backend="sim")
    hog = c.submit(mk_job("hog", mem_gb=10.0, est=4.0))
    w = c.submit(mk_job("w", mem_gb=10.0, est=1.0))
    assert c.sched.waiting_count() == 1
    assert w.cancel() is True
    assert w.status is JobStatus.CANCELLED
    assert c.sched.waiting_count() == 0
    uid = w.job.tasks[0].uid
    assert uid not in c.sched._admit_cbs and uid not in c.sched._epochs
    r = c._sim.drain()
    assert hog.status is JobStatus.DONE
    assert r.completed == 1 and r.cancelled == 1 and r.crashed == 0


# ---------------------------------------------------------------------------
# priority / deadline ordering (enforced in the waiter queue itself)
# ---------------------------------------------------------------------------

def _ordering_trace(cluster, *, est=0.01, body=None):
    """One exclusive device; jobs park while 'first' runs, then are admitted
    strictly in queue-rank order. Returns expected admission order."""
    mk = (lambda n: live_ej(n, demand=1.0, sleep=0.004, body=body)) \
        if cluster.backend == "live" else \
        (lambda n: mk_job(n, demand=1.0, est=est))
    cluster.submit(mk("first"))
    cluster.submit(mk("low-a"), priority=0)
    cluster.submit(mk("low-b"), priority=0)
    cluster.submit(mk("hi-late"), priority=5)        # overtakes low-a/low-b
    cluster.submit(mk("hi-edf-9"), priority=5, deadline_s=9.0)
    cluster.submit(mk("hi-edf-1"), priority=5, deadline_s=1.0)
    cluster.submit(mk("low-edf"), priority=0, deadline_s=3.0)
    return ["first", "hi-edf-1", "hi-edf-9", "hi-late",
            "low-edf", "low-a", "low-b"]


def test_priority_inversion_high_submitted_late_overtakes():
    """A high-priority job submitted AFTER parked low-priority waiters is
    admitted before them — the queue reorders, not the caller."""
    gate = threading.Event()
    c = Cluster(MGBAlg2Scheduler(1), workers=1, trace=True)
    # only "first" actually waits on the gate — everyone else starts after
    # gate.set() and returns immediately
    expected = _ordering_trace(c, body=lambda d: gate.wait(0.2))
    gate.set()
    c.drain()
    assert admission_order(c.trace.events()) == expected
    assert all(h.status is JobStatus.DONE for h in c.handles)
    c.shutdown()


def test_sim_edf_and_priority_ordering():
    c = Cluster(MGBAlg2Scheduler(1), workers=8, backend="sim", trace=True)
    expected = _ordering_trace(c)
    c.drain()
    assert admission_order(c.trace.events()) == expected


def test_live_and_sim_same_admission_order_for_same_trace():
    """Acceptance criterion: the two backends replay one submission trace
    into the SAME admission order (they share the scheduler's queue) —
    asserted through the obs.replay parity differ over each backend's
    event stream."""
    live = Cluster(MGBAlg2Scheduler(1), workers=1, trace=True)
    _ordering_trace(live)
    live.drain()
    live.shutdown()
    sim = Cluster(MGBAlg2Scheduler(1), workers=8, backend="sim", trace=True)
    _ordering_trace(sim)
    sim.drain()
    div = first_divergence(admission_order(live.trace.events()),
                           admission_order(sim.trace.events()))
    assert div is None, div


def test_deadline_is_ordering_hint_not_enforcement():
    """A missed deadline does not kill the job — EDF only ranks admission."""
    c = Cluster(MGBAlg2Scheduler(1), workers=4, backend="sim")
    c.submit(mk_job("hog", demand=1.0, est=10.0))
    late = c.submit(mk_job("late", demand=1.0, est=1.0), deadline_s=0.5)
    c.drain()
    assert late.status is JobStatus.DONE          # ran anyway, late
    assert late.records[0].t_start > 0.5


# ---------------------------------------------------------------------------
# drain() vs late submit()
# ---------------------------------------------------------------------------

def test_drain_vs_late_submit_race():
    """A submit racing drain() is never lost: drain returns only when the
    in-flight count is zero, so the late job either extends the drain or
    lands after it — both complete."""
    c = Cluster(MGBAlg3Scheduler(2), workers=2)
    for i in range(8):
        c.submit(live_ej(f"early{i}", sleep=0.01))
    late = []

    def late_submitter():
        for i in range(8):
            late.append(c.submit(live_ej(f"late{i}", sleep=0.002)))
            time.sleep(0.004)

    th = threading.Thread(target=late_submitter)
    th.start()
    c.drain()
    th.join()
    c.drain()                                     # catch stragglers
    assert all(h.status is JobStatus.DONE for h in c.handles)
    assert len(c.handles) == 16
    assert all(d.used_hbm == 0 for d in c.sched.devices)
    c.shutdown()


def test_submit_after_drain_and_shutdown_restarts_pool():
    c = Cluster(MGBAlg3Scheduler(1), workers=1)
    h1 = c.submit(live_ej("a", sleep=0.001))
    c.shutdown()
    assert h1.status is JobStatus.DONE
    h2 = c.submit(live_ej("b", sleep=0.001))      # pool restarts
    assert h2.result(timeout=5.0)[0].task == "b"
    c.shutdown()


# ---------------------------------------------------------------------------
# empty-tasks jobs (satellite regression)
# ---------------------------------------------------------------------------

def test_empty_job_finishes_immediately_live():
    c = Cluster(MGBAlg3Scheduler(1), workers=1)
    h = c.submit(ExecJob(job=Job(tasks=[], name="empty"), runners=[]))
    recs = h.result(timeout=5.0)
    assert h.status is JobStatus.DONE
    assert len(recs) == 1 and recs[0].device == -1 and not recs[0].crashed
    assert recs[0].t_start == recs[0].t_end
    c.shutdown()


def test_empty_job_finishes_immediately_sim():
    c = Cluster(MGBAlg3Scheduler(1), workers=1, backend="sim")
    h = c.submit(Job(tasks=[], name="empty"))
    assert h.status is JobStatus.DONE
    assert len(h.records) == 1 and h.records[0].device == -1
    r = c._sim.drain()
    assert r.completed == 1 and r.crashed == 0


def test_executor_run_empty_tasks_job_zeroed_record():
    """The batch shim path hits the same fix: no runners[0] IndexError."""
    ex = Executor(MGBAlg3Scheduler(2), workers=2)
    jobs = [ExecJob(job=Job(tasks=[], name="e0"), runners=[]),
            ExecJob(job=mk_job("real"), runners=[lambda d: None])]
    stats = ex.run(jobs)
    assert stats["completed"] == 2 and stats["crashed"] == 0
    assert any(r.job == "e0" and r.device == -1 and not r.crashed
               for r in ex.records)


def test_simulator_run_empty_metrics_guarded():
    """Satellite: SimResult means stay finite with zero completions."""
    r = Simulator(MGBAlg3Scheduler(2), workers=2).run([])
    assert r.completed == 0 and r.crashed == 0
    assert r.makespan == 0.0 and r.throughput == 0.0
    assert r.mean_turnaround == 0.0 and r.mean_slowdown_pct == 0.0
    assert r.utilization == 0.0
    r2 = Simulator(MGBAlg3Scheduler(2), workers=2).run(
        [Job(tasks=[], name="e")])
    assert r2.completed == 1 and r2.mean_slowdown_pct == 0.0


# ---------------------------------------------------------------------------
# the device table on an accelerator; runner errors
# ---------------------------------------------------------------------------

class _FakeChip:
    """Stands in for one attached accelerator: a platform other than the
    CPU, and the usable HBM it reports."""
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def __init__(self, bytes_limit):
        self._limit = bytes_limit

    def memory_stats(self):
        return {"bytes_limit": self._limit}


@pytest.mark.parametrize("num_devices,hbm_gb,limit_gb,refused", [
    (2, 8.0, 15.0, True),     # more scheduler devices than chips
    (1, 16.0, 15.0, True),    # more HBM per device than the chip reports
    (1, 15.0, 15.0, False),   # one device per chip, at the chip's limit
])
def test_live_cluster_device_table_checked_against_chips(
        monkeypatch, num_devices, hbm_gb, limit_gb, refused):
    """On an accelerator the live Cluster maps one scheduler device to one
    chip and refuses a table that promises memory no chip has (on the CPU
    backend the same tables are virtualized round-robin)."""
    import jax
    chip = _FakeChip(int(limit_gb * GB))
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [chip])
    sched = MGBAlg3Scheduler(num_devices, hbm_per_device=int(hbm_gb * GB))
    if refused:
        with pytest.raises(ValueError, match="attached|bytes_limit"):
            Cluster(sched, workers=1)
        return
    c = Cluster(sched, workers=1)
    assert c.jax_device(0) is chip
    c.shutdown()


def test_runner_exception_surfaces_in_job_error():
    """A runner's exception is kept on job.error and on the CRASH event, so
    a compile error or a real RESOURCE_EXHAUSTED is not a bare count."""
    from repro.obs import events as ev

    def boom(device):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    c = Cluster(MGBAlg3Scheduler(1), workers=1, trace=True)
    h = c.submit(live_ej("bad", body=boom))
    h.result(timeout=5.0)
    c.drain()
    assert h.status is JobStatus.CRASHED
    assert "RuntimeError: RESOURCE_EXHAUSTED: out of HBM" in h.job.error
    crashes = [e for e in c.trace.events() if e.kind == ev.CRASH]
    assert crashes and crashes[0].data["error"] == h.job.error
    c.shutdown()


# ---------------------------------------------------------------------------
# compatibility shim
# ---------------------------------------------------------------------------

def test_run_shim_metrics_keys_unchanged():
    ex = Executor(MGBAlg3Scheduler(2), workers=2)
    stats = ex.run([live_ej(f"j{i}", sleep=0.002) for i in range(6)])
    assert set(stats) >= {"makespan_s", "throughput_jobs_per_s", "completed",
                          "crashed", "mean_turnaround_s", "sched_attempts"}
    assert stats["completed"] == 6 and stats["crashed"] == 0
    # run() is submit-all-then-drain: the pool is torn down afterwards
    assert not ex._running


# ---------------------------------------------------------------------------
# property tests: queue-rank invariants
# ---------------------------------------------------------------------------

@given(seed=st.integers(0, 10_000), n=st.integers(3, 16))
@settings(max_examples=20, deadline=None)
def test_property_stable_fifo_within_class(seed, n):
    """Same priority, no deadlines => admission order is exactly arrival
    order, whatever the priorities of OTHER classes interleaved."""
    import random
    rng = random.Random(seed)
    sched = MGBAlg2Scheduler(1)
    hog = mk_task("hog", demand=1.0)
    assert sched.task_begin(hog) == 0
    admitted = []
    cb = lambda t, dev, epoch: admitted.append(t.name)
    arrivals = []
    for i in range(n):
        pri = rng.choice([0, 0, 0, 3])
        t = mk_task(f"t{i}", demand=1.0)
        t.priority = pri
        arrivals.append((pri, t.name))
        assert not sched.admit_or_enqueue(t, cb)
    sched.task_end(hog)
    while sched.waiting_count():
        resident = [t for d in sched.devices for t in d.residents.values()]
        sched.task_end(resident[0])
    for t in [t for d in sched.devices for t in d.residents.values()]:
        sched.task_end(t)
    per_class = lambda p: [nm for pr, nm in arrivals if pr == p]
    got_class = lambda p: [nm for nm in admitted
                           if nm in set(per_class(p))]
    assert got_class(0) == per_class(0)
    assert got_class(3) == per_class(3)
    # and every class-3 task beat every class-0 task
    if per_class(3) and per_class(0):
        assert max(admitted.index(nm) for nm in per_class(3)) \
            < min(admitted.index(nm) for nm in per_class(0))


@given(seed=st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_property_eviction_restart_front_of_its_class_only(seed):
    """An evicted resident re-enters at the front of ITS priority class:
    ahead of same-priority waiters (even deadlined ones), never ahead of a
    higher class."""
    import random
    rng = random.Random(seed)
    sched = MGBAlg3Scheduler(2)
    admitted = []
    cb = lambda t, dev, epoch: admitted.append((t.name, dev))
    victim = mk_task("victim", mem_gb=9.0)
    victim.priority = 1
    assert sched.admit_or_enqueue(victim, cb)
    dev0 = victim.device
    other = mk_task("other", mem_gb=9.0)
    assert sched.admit_or_enqueue(other, cb)      # fills the second device
    # park waiters in seeded order: some class 1 (victim's), some class 2
    waiters = []
    for i in range(rng.randint(2, 6)):
        pri = rng.choice([1, 1, 2])
        t = mk_task(f"w{i}", mem_gb=9.0)
        t.priority = pri
        t.deadline_t = rng.choice([None, float(i)])
        waiters.append((pri, t.name))
        assert not sched.admit_or_enqueue(t, cb)
    sched.mark_dead(dev0)                         # victim re-enters class 1
    order = [t.name for t in sched.waiting_tasks()]
    pos = {nm: i for i, nm in enumerate(order)}
    assert "victim" in pos                        # still parked (no room)
    for pri, nm in waiters:
        if pri == 1:      # victim leads its own class, even past deadlines
            assert pos["victim"] < pos[nm]
        else:             # ...but never jumps the higher class
            assert pos[nm] < pos["victim"]
    # release everything; nothing deadlocks and accounting zeroes out
    sched.task_end(other)
    while sched.waiting_count():
        resident = [t for d in sched.devices for t in d.residents.values()]
        if not resident:
            break
        sched.task_end(resident[0])
    for t in [t for d in sched.devices for t in d.residents.values()]:
        sched.task_end(t)
    assert all(d.used_hbm == 0 and d.used_slots == 0 for d in sched.devices)

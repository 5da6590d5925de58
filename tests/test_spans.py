"""The program's timing spans (``repro.obs.spans``), the executor's
admission stamp and the decode step's named scopes, on the CPU.

The span tree is recorded under ``jax.profiler`` from a live ``Cluster``
and ``ServeEngine`` over a test-size ``JaxModel``, and read back with the
benchmark's reduction (``benchmarks/chip/spantrace.py``).
"""
import contextlib
import gc
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.core.cluster import Cluster
from repro.core.executor import ExecJob
from repro.core.scheduler import MGBAlg3Scheduler
from repro.core.task import Job, ResourceVector, Task, UnitTask
from repro.models import decode as D
from repro.models.model import init_params
from repro.obs import spans
from repro.obs.spans import span
from repro.serve.decode import make_prefill_step
from repro.serve.engine import SLO, JaxModel, RequestStatus, ServeEngine

CHIP = os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks", "chip")
sys.path.insert(0, os.path.abspath(CHIP))
import spantrace as ST  # noqa: E402

GB = 1 << 30


def _traced(tmp, body):
    """Run ``body`` under a profiler session inside the harness's window
    span and read the trace back."""
    jax.profiler.start_trace(str(tmp))
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            body()
    finally:
        jax.profiler.stop_trace()
    return ST.load(str(tmp))


def _inside(tr, outer, name):
    """Spans called ``name`` nested in span ``outer`` on its thread."""
    return [s for s in tr["spans"] if s[0] == name and s[3] == outer[3]
            and outer[1] <= s[1] and s[2] <= outer[2]]


def test_span_is_the_shared_null_context_without_a_profiler(tmp_path):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    outside = span("repro.test.outside", rid=1)
    assert outside is spans.NULL_SPAN
    assert span("repro.test.other") is outside

    def body():
        with span("repro.test.inside", rid=2) as sp:
            sp.set_metadata(rows=3)
    with outside as sp:
        sp.set_metadata(rows=1)
        tr = _traced(tmp_path, body)
    names = [s[0] for s in tr["spans"]]
    assert "repro.test.outside" not in names
    inside = [s for s in tr["spans"] if s[0] == "repro.test.inside"]
    assert len(inside) == 1 and inside[0][4] == {"rid": 2, "rows": 3}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Three requests of one prompt length through the live engine, under
    the profiler."""
    cfg = get_arch("zamba2-2.7b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    model = JaxModel(cfg, params, max_batch=2, max_seq=32,
                     attn_impl="flash_jnp")
    c = Cluster(MGBAlg3Scheduler(1, hbm_per_device=64 * GB), workers=2)
    eng = ServeEngine(c, model, max_batch=2, slo=SLO(600.0, 600.0))
    rng = np.random.default_rng(0)
    reqs = []

    def body():
        for _ in range(3):
            reqs.append(eng.submit(prompt=rng.integers(
                0, cfg.vocab, (1, 8), dtype=np.int32), gen_len=3))
        eng.drain(timeout_s=300.0)
        gc.collect()
    try:
        tr = _traced(tmp_path_factory.mktemp("served"), body)
    finally:
        eng.shutdown()
        c.shutdown()
    assert all(r.status is RequestStatus.DONE for r in reqs)
    return tr, reqs


def test_submit_holds_its_probe_and_admission(served):
    tr, reqs = served
    subs = [s for s in tr["spans"] if s[0] == "repro.serve.submit"]
    assert [s[4]["rid"] for s in subs] == [r.rid for r in reqs]
    assert all(s[3] == tr["harness_thread"] for s in subs)
    assert all(s[4]["prompt_len"] == 8 for s in subs)
    hits = []
    for s in subs:
        (probe,) = _inside(tr, s, "repro.probe")
        hits.append(probe[4]["hit"])
        assert len(_inside(tr, s, "repro.sched.admit")) == 1
    # one prompt length: the first probe compiles, the others hit
    assert hits == [0, 1, 1]


def test_pump_holds_adopt_step_and_readback(served):
    tr, reqs = served
    pumps = [s for s in tr["spans"] if s[0] == "repro.serve.pump"]
    stepped = [p for p in pumps if p[4]["rows"] > 0]
    assert stepped and all(p[3] == tr["harness_thread"] for p in pumps)
    for p in stepped:
        assert len(_inside(tr, p, "repro.serve.step")) == 1
        (rb,) = _inside(tr, p, "repro.serve.readback")
        (st,) = _inside(tr, p, "repro.serve.step")
        assert st[2] <= rb[1]
        assert p[4]["emitted"] == p[4]["rows"]
        assert p[4]["kv"] >= 8 * p[4]["rows"]
    adopted = [a[4]["rid"] for p in pumps
               for a in _inside(tr, p, "repro.serve.adopt")]
    assert sorted(adopted) == sorted(r.rid for r in reqs)
    # every decode token came from a stepped pump (the first from prefill)
    assert sum(p[4]["emitted"] for p in pumps) == sum(
        r.gen_len - 1 for r in reqs)


def test_retire_holds_the_shrink(served):
    tr, reqs = served
    retires = [s for s in tr["spans"] if s[0] == "repro.serve.retire"]
    assert sum(s[4]["n"] for s in retires) == len(reqs)
    for s in retires:
        shrinks = _inside(tr, s, "repro.sched.shrink")
        assert len(shrinks) == s[4]["n"]
        for sh in shrinks:
            assert len(_inside(tr, sh, "repro.sched.end")) == 1


def test_prefills_run_on_pool_threads(served):
    tr, reqs = served
    runs = [s for s in tr["spans"] if s[0] == "repro.exec.run"]
    assert sorted(s[4]["task"] for s in runs) == sorted(
        f"prefill/{r.rid}" for r in reqs)
    assert all(s[3] != tr["harness_thread"] for s in runs)
    # each slot join grew a decode loop through the scheduler
    grows = [s for s in tr["spans"] if s[0] == "repro.sched.grow"]
    assert len(grows) == len(reqs)
    assert all(len(_inside(tr, g, "repro.sched.admit")) == 1 for g in grows)


def test_garbage_collections_are_spans(served):
    tr, _ = served
    gcs = [s for s in tr["spans"] if s[0] == "repro.gc"]
    assert any(s[4]["generation"] == 2 for s in gcs)  # the gc.collect()
    assert {s[4]["generation"] for s in gcs} <= {0, 1, 2}
    assert ST.gc_pause_share(tr) > 0


def test_span_readings_at_test_size(served):
    tr, reqs = served
    assert ST.probe_ms(tr) > 0
    assert ST.sched_call_ms(tr) > 0
    assert ST.pump_host_ms(tr) > 0
    calls = ST.outermost(ST.in_window(tr), "repro.sched.")
    names = [s[0] for s in calls]
    # admissions from submit, grows from prefill completions, shrinks from
    # retires and the prefills' ends: none of them nested in another
    assert names.count("repro.sched.grow") == len(reqs)
    assert "repro.sched.admit" in names and "repro.sched.shrink" in names


def _job(name, mem_gb):
    vec = ResourceVector(hbm_bytes=int(mem_gb * GB), flops=1e9,
                         bytes_accessed=1e9, est_seconds=0.005,
                         core_demand=0.5, bw_demand=0.5)
    task = Task(units=[UnitTask(fn=None, memobjs=frozenset({name}),
                                resources=vec, name=name)], name=name)
    return ExecJob(job=Job(tasks=[task], name=name),
                   runners=[lambda device: time.sleep(0.01)])


def test_exec_records_order_queue_admit_start():
    """Jobs that fill the device park in the scheduler: their admission
    stamp lies between submission and the start of execution."""
    c = Cluster(MGBAlg3Scheduler(1, hbm_per_device=16 * GB), workers=1)
    handles = [c.submit(_job(f"j{i}", 10.0)) for i in range(4)]
    c.drain()
    c.shutdown()
    recs = [h.records[-1] for h in handles]
    assert all(r.started for r in recs)
    for r in recs:
        assert r.t_queue <= r.t_admit <= r.t_start <= r.t_end
    # one job at a time fits: the later ones waited in the scheduler
    assert max(r.t_admit - r.t_queue for r in recs) > 0.005


def _canonical(hlo: str) -> str:
    """HLO text without metadata (op_name, source locations and the
    tables they index) and with instructions numbered by first use."""
    hlo = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                 r"(.+\n)*", "\n", hlo)
    hlo = re.sub(r",? metadata=\{[^}]*\}", "", hlo)
    names = {}
    for m in re.finditer(r"%([\w.\-]+)", hlo):
        names.setdefault(m.group(1), f"i{len(names)}")
    return re.sub(r"%([\w.\-]+)", lambda m: "%" + names[m.group(1)], hlo)


def _decode_and_prefill_hlo(cfg):
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: D.init_cache(cfg, 2, 64))
    rows = jax.ShapeDtypeStruct((2,), jnp.int32)

    def _decode(p, c, t, pos):
        return D.decode_step(p, cfg, c, t, pos)
    dec = jax.jit(_decode).lower(params, cache, rows, rows).compile()
    pre = jax.jit(make_prefill_step(cfg, attn_impl="flash_jnp")).lower(
        params, {"tokens": jax.ShapeDtypeStruct((1, 32), jnp.int32)}
    ).compile()
    return dec.as_text(), pre.as_text()


@pytest.mark.parametrize("arch", ["zamba2-2.7b", "falcon-mamba-7b",
                                  "qwen1.5-32b"])
def test_named_scopes_change_only_metadata(arch, monkeypatch):
    cfg = get_arch(arch).reduced()
    scoped = _decode_and_prefill_hlo(cfg)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _decode_and_prefill_hlo(cfg)
    for a, b in zip(scoped, plain):
        assert _canonical(a) == _canonical(b)
    found = set(ST.hlo_scopes(scoped[0]).values()) - {ST.PLUMBING}
    want = {"embed", "norm", "mamba", "state_write", "attn", "kv_write",
            "mlp", "logits"}
    if cfg.family == "ssm":
        want -= {"attn", "kv_write", "mlp"}
    elif cfg.family != "hybrid":
        want -= {"mamba", "state_write"}
    assert found == want

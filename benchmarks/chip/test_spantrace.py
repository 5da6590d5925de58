"""The span reductions (``spantrace.py``) against brute force on synthetic
traces, and on a trace of the engine recorded on a TPU v5e by
``span_report.py --record`` (``testdata/serve_trace.*``: a short window of
the test-size zamba2 layout through the live Cluster and ServeEngine).

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q \
        benchmarks/chip/test_spantrace.py
"""
from __future__ import annotations

import json
import os
import random
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import spantrace as ST  # noqa: E402
import trace as TRC  # noqa: E402

SERVE = os.path.join(HERE, "testdata", "serve_trace")


def _nested(rng, lo, hi, depth, name="op"):
    """Random properly nested events in [lo, hi) (ticks of 10 ns), no two
    alike."""
    out, t = [], lo
    while t < hi - 40 and rng.random() < 0.8:
        s = t + rng.randrange(0, 20) * 10
        e = min(s + rng.randrange(2, 40) * 10, hi)
        if e <= s:
            break
        out.append([f"{name}.{len(out)}", s, e])
        if depth:
            out += _nested(rng, s + 10, e, depth - 1, name + "x")
        t = e
    return out


def _brute_self(events, i):
    """Ticks of event ``i`` that no event strictly inside it covers."""
    _, s, e = events[i][:3]
    inner = [(a, b) for j, (_, a, b, *_) in enumerate(events)
             if j != i and s <= a and b <= e and (a, b) != (s, e)]
    return sum(10 for t in range(int(s), int(e), 10)
               if not any(a <= t < b for a, b in inner))


@pytest.mark.parametrize("seed", range(5))
def test_self_times_match_brute_force(seed):
    rng = random.Random(seed)
    events = _nested(rng, 0, 3000, 3)
    rng.shuffle(events)
    got = ST.self_times(events)
    assert got == [_brute_self(events, i) for i in range(len(events))]
    # self times of a properly nested line add up to its busy time
    assert sum(got) == pytest.approx(sum(
        e - s for s, e in TRC._union([(a, b) for _, a, b in events])))


def _synthetic(seed=0):
    """A trace dict: one device line, spans on two host threads."""
    rng = random.Random(seed)
    ops = _nested(rng, 1000, 9000, 2)
    main = [s + ["main#0", {}] for s in _nested(rng, 0, 10000, 2, "repro.a")]
    pool = [s + ["pool#1", {}] for s in _nested(rng, 0, 10000, 1, "repro.b")]
    host = [["bench.pump", 500, 4000], ["bench.wait", 4000, 10000]]
    return {"window": [0, 10000], "host": host,
            "devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
            "spans": sorted(main + pool, key=lambda s: (s[1], -s[2])),
            "harness_thread": "main#0"}


@pytest.mark.parametrize("seed", range(3))
def test_span_self_time_stays_on_its_thread(seed):
    tr = _synthetic(seed)
    got = ST.span_self(tr)
    for th in ("main#0", "pool#1"):
        idx = [i for i, s in enumerate(tr["spans"]) if s[3] == th]
        line = [tr["spans"][i] for i in idx]
        assert [got[i] for i in idx] == [_brute_self(line, k)
                                          for k in range(len(line))]


@pytest.mark.parametrize("seed", range(3))
def test_idle_by_label_fills_the_idle_time(seed):
    tr = _synthetic(seed)
    by = ST.idle_by_label(tr)
    assert sum(by.values()) == pytest.approx(
        TRC.window_s(tr) - TRC.busy_s(tr), abs=1e-12)
    gaps = ST.idle_gaps(tr, k=10**6)
    assert sum(g for _, g in gaps) == pytest.approx(sum(by.values()))


def test_host_label_prefers_the_harness_threads_span():
    tr = {"window": [0, 100], "devices": {}, "harness_thread": "main#0",
          "host": [["bench.pump", 0, 100]],
          "spans": [["repro.serve.pump", 10, 30, "main#0", {}],
                    ["repro.serve.readback", 20, 30, "main#0", {}],
                    ["repro.exec.run", 0, 60, "pool#1", {}],
                    ["repro.gc", 40, 45, "pool#1", {}]]}
    assert ST.host_label(tr, 25) == "repro.serve.readback"   # innermost
    assert ST.host_label(tr, 15) == "repro.serve.pump"       # harness first
    assert ST.host_label(tr, 42) == "repro.gc"               # any thread
    assert ST.host_label(tr, 50) == "repro.exec.run"
    assert ST.host_label(tr, 80) == "bench.pump"             # bench.* next
    tr["host"] = []
    assert ST.host_label(tr, 80) == "host-other"


def test_outermost_skips_nested_calls():
    spans = [["repro.sched.shrink", 0, 10, "a", {}],
             ["repro.sched.end", 1, 9, "a", {}],
             ["repro.sched.grow", 2, 4, "a", {}],
             ["repro.sched.admit", 5, 6, "b", {}],
             ["repro.serve.pump", 0, 20, "b", {}]]
    assert [s[0] for s in ST.outermost(spans, "repro.sched.")] == [
        "repro.sched.shrink", "repro.sched.admit"]


def test_the_decode_trace_reads_as_before():
    """A trace without program spans gets the harness's labels."""
    tr = ST.load(os.path.join(HERE, "testdata", "decode_trace.xplane.pb"))
    assert tr["spans"] == [] and tr["harness_thread"] is not None
    assert ST.idle_gaps(tr) == TRC.idle_gaps(tr)
    old = TRC.load(os.path.join(HERE, "testdata", "decode_trace.xplane.pb"))
    assert {k: tr[k] for k in old} == old
    assert ST.gc_pause_share(tr) is None and ST.probe_ms(tr) is None


HLO = """HloModule jit__decode, is_scheduled=true

%fused_write (param_0.1: bf16[8,4], param_1.2: pred[8,4]) -> (bf16[1,8,4]) {
  %param_0.1 = bf16[8,4]{1,0} parameter(0)
  %param_1.2 = pred[8,4]{1,0} parameter(1)
  %select_n.5 = bf16[8,4]{1,0} select(%param_1.2, %param_0.1, %param_0.1), metadata={op_name="jit(_decode)/while/body/attn/kv_write/jit(_where)/select_n" stack_frame_id=3}
  %bitcast.6 = bf16[1,8,4]{2,1,0} bitcast(%select_n.5), metadata={op_name="jit(_decode)/while/body/broadcast_in_dim"}
  ROOT %tuple.7 = (bf16[1,8,4]{2,1,0}) tuple(%bitcast.6)
}

%fused_slice (param_0.3: bf16[9,8,4], param_1.4: s32[]) -> bf16[1,8,4] {
  %param_0.3 = bf16[9,8,4]{2,1,0} parameter(0)
  %param_1.4 = s32[] parameter(1)
  ROOT %dynamic_slice.8 = bf16[1,8,4]{2,1,0:T(8,128)(2,1)} dynamic-slice(%param_0.3, %param_1.4, %param_1.4, %param_1.4), dynamic_slice_sizes={1,8,4}, metadata={op_name="jit(_decode)/while/body/dynamic_slice"}
}

ENTRY %main.9 (p.0: bf16[9,8,4], p.1: s32[], p.2: bf16[8,4], p.3: pred[8,4]) -> bf16[1,8,4] {
  %p.0 = bf16[9,8,4]{2,1,0} parameter(0), metadata={op_name="params"}
  %p.1 = s32[] parameter(1)
  %p.2 = bf16[8,4]{1,0} parameter(2)
  %p.3 = pred[8,4]{1,0} parameter(3)
  %fusion.10 = (bf16[1,8,4]{2,1,0:T(8,128)(2,1)}) fusion(%p.2, %p.3), kind=kLoop, calls=%fused_write, metadata={op_name="jit(_decode)/while/body/broadcast_in_dim"}
  %slice_fusion.11 = bf16[1,8,4]{2,1,0} fusion(%p.0, %p.1), kind=kLoop, calls=%fused_slice, metadata={op_name="jit(_decode)/while/body/dynamic_slice"}
  %copy.12 = bf16[1,8,4]{1,2,0} copy(%slice_fusion.11)
  %mul.13 = bf16[8,4]{1,0} multiply(%p.2, %p.2), metadata={op_name="jit(_decode)/while/body/closed_call/mamba/norm/mul"}
  ROOT %gte.14 = bf16[1,8,4]{2,1,0} get-tuple-element(%fusion.10), index=0
}
"""


def test_hlo_scopes_name_a_fusion_by_its_work():
    scopes = ST.hlo_scopes(HLO)
    assert scopes["fusion.10"] == "kv_write"        # not its stacking bitcast
    assert scopes["slice_fusion.11"] == ST.PLUMBING
    assert scopes["copy.12"] == ST.PLUMBING          # a copy outside a fusion
    assert scopes["mul.13"] == "norm"
    assert ST.parse_hlo(HLO)["main.9"]["root"] == "gte.14"


def test_scope_of_takes_the_innermost_model_scope():
    assert ST.scope_of("jit(_decode)/while/body/closed_call/mamba/norm/mul") \
        == "norm"
    assert ST.scope_of("jit(_decode)/while/body/attn/kv_write/select_n") \
        == "kv_write"
    assert ST.scope_of("jit(_decode)/while/body/dynamic_slice") == ST.PLUMBING
    assert ST.plumbing_share({ST.PLUMBING: 3.0}) is None
    assert ST.plumbing_share({ST.PLUMBING: 1.0, "mlp": 3.0}) == 25.0


# -- the trace recorded on the chip ----------------------------------------

@pytest.fixture(scope="module")
def served():
    with open(SERVE + ".json") as f:
        meta = json.load(f)
    with open(SERVE + ".hlo.txt") as f:
        hlo = f.read()
    return ST.load(SERVE + ".xplane.pb"), meta, hlo


def _inside(tr, outer, name):
    return [s for s in tr["spans"] if s[0] == name and s[3] == outer[3]
            and outer[1] <= s[1] and s[2] <= outer[2]]


def test_serve_trace_span_tree(served):
    tr, meta, _ = served
    subs = [s for s in tr["spans"] if s[0] == "repro.serve.submit"]
    assert len(subs) >= 3 and all(s[3] == tr["harness_thread"] for s in subs)
    for s in subs:
        assert len(_inside(tr, s, "repro.probe")) == 1
        assert len(_inside(tr, s, "repro.sched.admit")) == 1
    stepped = [s for s in tr["spans"] if s[0] == "repro.serve.pump"
               and s[4]["rows"] > 0]
    assert stepped
    for p in stepped:
        assert len(_inside(tr, p, "repro.serve.step")) == 1
        assert len(_inside(tr, p, "repro.serve.readback")) == 1
    for r in (s for s in tr["spans"] if s[0] == "repro.serve.retire"):
        assert len(_inside(tr, r, "repro.sched.shrink")) == r[4]["n"]
    runs = [s for s in tr["spans"] if s[0] == "repro.exec.run"]
    assert runs and all(s[3] != tr["harness_thread"] for s in runs)


def test_serve_trace_op_self_time_is_the_busy_time(served):
    tr, _, _ = served
    dev = next(iter(tr["devices"].values()))
    lo, hi = tr["window"]
    ops = [o for o in dev["ops"] if lo <= o[1] and o[2] <= hi]
    got = ST.self_times(ops)
    assert all(v >= 0 for v in got)
    # timestamps are whole nanoseconds: a few body ops end 1 ns after
    # their while op
    assert sum(got) == pytest.approx(
        sum(e - s for s, e in TRC._union([(a, b) for _, a, b in ops])),
        abs=100)


def test_serve_trace_scopes_join_the_hlo(served):
    tr, meta, hlo = served
    scopes = ST.decode_scopes(tr, hlo, meta["module_prefix"])
    total = sum(scopes.values())
    secs, _ = TRC.module_s(tr, meta["module_prefix"])
    assert total == pytest.approx(secs, rel=0.02)
    assert scopes.get(ST.UNMAPPED, 0.0) <= 0.1 * total
    assert {"mamba", "attn", "mlp"} <= set(scopes)
    assert 0 < ST.plumbing_share(scopes) < 100


def test_serve_trace_idle_is_named(served):
    tr, _, _ = served
    by = ST.idle_by_label(tr)
    assert sum(by.values()) == pytest.approx(
        TRC.window_s(tr) - TRC.busy_s(tr), rel=1e-9)
    # idle time inside the harness's calls into the engine is named by the
    # program's spans, but for the harness's own few microseconds per call
    label = ST.labeler(tr)
    inside = named = 0.0
    for s, e in ST.idle_stretches(tr):
        if TRC.host_label(tr, (s + e) / 2) in ("bench.pump", "bench.submit"):
            inside += e - s
            named += (e - s) * label((s + e) / 2).startswith(ST.SPAN_PREFIX)
    assert inside > 0 and named >= 0.99 * inside
    assert ST.probe_ms(tr) > 0 and ST.sched_call_ms(tr) > 0
    assert ST.pump_host_ms(tr) > 0 and ST.gc_pause_share(tr) is not None


# -- the decode program rebuilt from shapes --------------------------------

def test_rebuilt_decode_program_is_the_engines():
    """``decode_hlo_text`` compiles the very program the engine runs: the
    same instructions with the same op_names."""
    import jax
    import numpy as np
    import harness
    import run
    import weights as W
    from repro.serve.engine import JaxModel
    cfg = run.load_json(HERE, "testdata", "zamba2-tiny.json")
    pc = harness.program_config(cfg)
    dev = jax.devices()[0]
    model = JaxModel(pc, W.make_params(cfg, 1, dev), max_batch=2,
                     max_seq=80)
    st = model.make_loop_state(2, dev)
    put = (lambda a: jax.device_put(np.asarray(a), dev))
    ran = model._decode.lower(st["params"], st["cache"], put(st["tokens"]),
                              put(st["pos"])).compile().as_text()
    rebuilt = ST.decode_hlo_text(pc, W.abstract_params(cfg), 2, 80, dev)
    assert ST.parse_hlo(ran) == ST.parse_hlo(rebuilt)
    assert set(ST.hlo_scopes(ran).values()) >= {
        "mamba", "attn", "mlp", "norm", ST.PLUMBING}

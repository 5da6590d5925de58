#!/usr/bin/env python3
"""A traced window of a cell, reduced with the program's own spans.

    python3 benchmarks/chip/span_report.py --workload <cell> --seed <n> \
        --seconds <s> [--out REPORT.json]
    python3 benchmarks/chip/span_report.py --record PREFIX [--seconds 2]

The first form runs a cell of ``BENCHMARK.json`` as ``run.py --trace 1``
does (the same harness, profiler and window) and prints, as its last
line, what ``spantrace`` reads from the trace: the span readings, the
device's idle time by the span open at each idle stretch, the longest idle
stretches, the decode program's op self time by the model's named scope,
and what one span costs with the profiler off and on. It runs no output
check.

The second form records the trace that ``test_spantrace.py`` reads: a
short window of the test-size zamba2 layout (``testdata/zamba2-tiny.json``
under ``testdata/chat.json``), with Python's function tracer and the
programs' HLO protos left out of the trace, written
to PREFIX.xplane.pb, the decode program's HLO text (compiled past the
compile cache, so that it carries this program's scopes) to PREFIX.hlo.txt
and a summary to PREFIX.json.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile
import time
import timeit

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def span_cost(n: int = 20000) -> dict:
    """Microseconds per ``with span(...)`` with one arg, the profiler off
    and on."""
    import jax
    from repro.obs.spans import span

    def once():
        with span("repro.cost", rid=1):
            pass
    off = timeit.timeit(once, number=n) / n
    d = tempfile.mkdtemp(prefix="span-cost-")
    jax.profiler.start_trace(d)
    try:
        on = timeit.timeit(once, number=n) / n
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    return {"off_us": off * 1e6, "on_us": on * 1e6}


def _profile_keeping(keep_to=None, python_tracer=True):
    """The harness's profiler, reading the trace with the program's spans
    (and copying its .xplane.pb to ``keep_to``)."""
    import harness
    import jax
    import spantrace

    class Profile(harness._Profile):
        def __init__(self, cell):
            self.cell = cell
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            if not python_tracer:       # a small trace: no Python calls,
                opts.python_tracer_level = 0    # no programs' HLO
                opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.annot = jax.profiler.TraceAnnotation("bench.traced")

        def stop(self):
            jax.profiler.stop_trace()
            try:
                src = spantrace.xplane(self.dir)
                if keep_to:
                    shutil.copy(src, keep_to)
                self.cell.trace_data = spantrace.load(src)
            finally:
                shutil.rmtree(self.dir, ignore_errors=True)
    return Profile


def _decode_hlo(cell) -> str:
    """The HLO text of the decode program the engine ran (its own jitted
    function, at the loop's arguments)."""
    import jax
    loop = next(iter(cell.engine.loops.values()))
    st = loop.state
    put = (lambda a: jax.device_put(a, st["device"]))
    return cell.model._decode.lower(
        st["params"], st["cache"], put(st["tokens"]), put(st["pos"])
    ).compile().as_text()


def window(cfg: dict, mix: dict, seed: int, seconds: float, keep_to=None,
           python_tracer: bool = True):
    """Build, warm and run one traced window; returns the cell (freed),
    the decode program's HLO text as it ran, and as ``spantrace`` rebuilds
    it from shapes."""
    import jax
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    import harness
    import spantrace
    import weights as W
    harness._Profile = _profile_keeping(keep_to, python_tracer)
    dev = jax.devices()[0]
    cell = harness.Cell(cfg, mix, seed, seconds, True)
    cell.device_kind = dev.device_kind
    cell.build()
    cell.warm()
    run.log("warmed")
    cell.run_window()
    run.log(f"window closed: {len(cell.served)} requests, "
            f"{len(cell.pumps)} decode steps")
    ran = _decode_hlo(cell)
    rebuilt = spantrace.decode_hlo_text(cell.pc, W.abstract_params(cfg),
                                        mix["rows"], mix["max_seq"], dev)
    recs = {}
    for h in cell.cluster.handles:
        if h.job.name.startswith("prefill/") and h.records:
            recs[int(h.job.name.split("/")[1])] = h.records[-1]
    cell.prefill_records = [recs.get(s.sr.rid) for s in cell.served]
    cell.free()
    return cell, ran, rebuilt


def _without_sources(hlo: str) -> str:
    """HLO text without its tables of source files and lines (the
    instructions' op_name metadata stays)."""
    return re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(.+\n)*", "\n", hlo)


def _same_ops(a: str, b) -> bool:
    """Whether two HLO texts hold the same instructions with the same
    op_names (what the scope join reads)."""
    import spantrace as ST
    return b is not None and ST.parse_hlo(a) == ST.parse_hlo(b)


def reduce(cell, hlo: str) -> dict:
    import harness
    import spantrace as ST
    import trace as TRC
    tr = cell.trace_data
    scopes = ST.decode_scopes(tr, hlo, harness.DECODE_MODULE)
    total = sum(scopes.values())
    busy = TRC.busy_s(tr)
    idle = TRC.window_s(tr) - busy
    by_label = ST.idle_by_label(tr)
    named = sum(v for k, v in by_label.items()
                if k.startswith(ST.SPAN_PREFIX) or k == "bench.wait")
    return {
        "window_s": TRC.window_s(tr), "busy_s": busy,
        "probe_ms": ST.probe_ms(tr),
        "sched_call_ms": ST.sched_call_ms(tr),
        "prefill_parked_ms": run.reader("prefill_parked_ms")(cell),
        "pump_host_ms": ST.pump_host_ms(tr),
        "gc_pause_share": ST.gc_pause_share(tr),
        "decode_plumbing_share": ST.plumbing_share(scopes),
        "decode_step_ms": run.reader("decode_step_ms.chat")(cell),
        "arrival_late_ms": run.reader("arrival_late_ms")(cell),
        "prefill_queue_ms": run.reader("prefill_queue_ms")(cell),
        "idle_s": idle,
        "idle_named_share": 100.0 * named / idle if idle else None,
        "idle_by_label": by_label,
        "idle_gaps": ST.idle_gaps(tr),
        "idle_gaps_bench": TRC.idle_gaps(tr),
        "decode_scopes": scopes,
        "decode_top_ops": _top_ops(tr, hlo),
        "decode_mapped_share": 100.0 * (total - scopes.get(ST.UNMAPPED, 0.0))
        / total if total else None,
        "spans": span_stats(tr),
    }


def _top_ops(tr: dict, hlo: str, k: int = 15) -> list:
    """The decode program's ``k`` ops of most self time: [instruction,
    scope, seconds]."""
    import harness
    import spantrace as ST
    scopes = ST.hlo_scopes(hlo)
    ops = ST.module_op_self(tr, harness.DECODE_MODULE)
    return [[n, scopes.get(n, ST.UNMAPPED), v] for n, v in
            sorted(ops.items(), key=lambda kv: -kv[1])[:k]]


def span_stats(tr: dict) -> dict:
    """Per span name in the window: count, mean / p50 / p90 / max duration
    and mean self time (ms); scheduler calls also as outermost calls."""
    import spantrace as ST
    spans = ST.in_window(tr)
    selfs = dict(zip(map(id, tr["spans"]), ST.span_self(tr)))
    groups: dict = {}
    for s in spans:
        groups.setdefault(s[0], []).append(s)
    for s in ST.outermost(spans, "repro.sched."):
        groups.setdefault("outermost " + s[0], []).append(s)
    out = {}
    for name, ss in sorted(groups.items()):
        d = sorted((e - b) * 1e-6 for _, b, e, _, _ in ss)
        out[name] = {"n": len(d), "mean_ms": sum(d) / len(d),
                     "p50_ms": d[len(d) // 2], "p90_ms": d[int(0.9 * len(d))],
                     "max_ms": d[-1],
                     "self_mean_ms": sum(selfs[id(s)] for s in ss)
                     * 1e-6 / len(ss)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out")
    ap.add_argument("--record", metavar="PREFIX")
    args = ap.parse_args(argv)
    run.configure_jax(run.ROOT)
    import jax
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind}}
    if args.record:
        cfg = run.load_json(HERE, "testdata", "zamba2-tiny.json")
        mix = run.load_json(HERE, "testdata", "chat.json")
        cell, ran, rebuilt = window(cfg, mix, args.seed, args.seconds,
                                    keep_to=args.record + ".xplane.pb",
                                    python_tracer=False)
        with open(args.record + ".hlo.txt", "w") as f:
            f.write(_without_sources(rebuilt or ran))
        out.update({"module_prefix": "jit__decode",
                    "recorded_on": dev.device_kind,
                    "by": "span_report.py --record",
                    "seconds": args.seconds, "seed": args.seed,
                    "requests": len(cell.served),
                    "decode_steps": len(cell.pumps),
                    "rebuilt_hlo_equal": _same_ops(ran, rebuilt)})
        with open(args.record + ".json", "w") as f:
            json.dump(out, f, indent=1)
    else:
        out["span_cost"] = span_cost()
        spec = run.load_spec()
        cs = run.find(spec["workloads"], args.workload, "workload")
        conf = run.find(spec["configs"], cs["config"], "config")
        cfg = run.load_json(run.ROOT, conf["file"])
        mix = run.load_json(HERE, "traffic", cs["traffic"] + ".json")
        t0 = time.time()
        cell, ran, rebuilt = window(cfg, mix, args.seed, args.seconds)
        out.update(workload=args.workload, seed=args.seed,
                   seconds=time.time() - t0,
                   rebuilt_hlo_equal=_same_ops(ran, rebuilt))
    # the rebuilt text carries this program's scopes even where the engine
    # loaded its decode program from another version's cache entry
    out.update(reduce(cell, rebuilt or ran))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

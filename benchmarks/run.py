"""Benchmark runner: one experiment per paper table/figure, printed summary,
JSON artifacts under benchmarks/results/, plus a consolidated
``BENCH_10.json`` of per-bench headline numbers so the perf trajectory is
tracked across PRs.

    PYTHONPATH=src python -m benchmarks.run [--only fig5]
    PYTHONPATH=src python -m benchmarks.run --only executor,gang,preempt --smoke
"""
from __future__ import annotations

import argparse
import numbers
import time
from typing import Any, Dict

from benchmarks import (
    bench_executor, bench_gang, bench_obs, bench_preempt, bench_profile,
    bench_sched_scale, bench_serve, bench_whatif, fig4_alg2_vs_alg3,
    fig5_throughput, fig6_nn_schedgpu, kernels_bench, table2_crashes,
    table3_turnaround, table4_slowdown,
)
from benchmarks.common import save_json
from repro.launch.compile_cache import enable_compile_cache

EXPERIMENTS = {
    "fig4": fig4_alg2_vs_alg3.run,
    "fig5": fig5_throughput.run,
    "table2": table2_crashes.run,
    "table3": table3_turnaround.run,
    "table4": table4_slowdown.run,
    "fig6": fig6_nn_schedgpu.run,
    "kernels": kernels_bench.run,
    "executor": bench_executor.run,
    "gang": bench_gang.run,
    "preempt": bench_preempt.run,
    "sched_scale": bench_sched_scale.run,
    "serve": bench_serve.run,
    "obs": bench_obs.run,
    "profile": bench_profile.run,
    "whatif": bench_whatif.run,
}

# experiments whose run() takes smoke= (tiny inputs, assert-only, no JSON);
# --smoke forwards to these and leaves the rest at full size
SMOKE_CAPABLE = frozenset({"executor", "gang", "obs", "preempt", "profile",
                           "sched_scale", "serve", "whatif"})


def _headline(result: Any, depth: int = 0) -> Any:
    """Distill an experiment's return value to its numeric scalars: dicts
    keep number-valued entries (one level of nesting), lists of row-dicts
    are keyed by their 'bench'/'config'/'name' labels. Anything else is
    dropped — the trajectory file wants comparable numbers, not blobs."""
    if isinstance(result, bool):
        return None
    if isinstance(result, numbers.Number):
        return result
    if isinstance(result, dict):
        out = {}
        for k, v in result.items():
            h = _headline(v, depth + 1) if depth < 2 else (
                v if isinstance(v, numbers.Number)
                and not isinstance(v, bool) else None)
            if h is not None and h != {}:
                out[str(k)] = h
        return out
    if isinstance(result, (list, tuple)) and depth < 2:
        out = {}
        for i, row in enumerate(result):
            if not isinstance(row, dict):
                continue
            label = "/".join(str(row[k]) for k in ("bench", "config", "name",
                                                   "engine", "depth")
                             if k in row) or str(i)
            h = _headline(row, depth + 1)
            if h:
                out[label] = h
        return out
    return None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated experiment list, e.g. "
                         f"'fig5' or 'executor,gang,preempt' "
                         f"(available: {', '.join(sorted(EXPERIMENTS))})")
    ap.add_argument("--smoke", action="store_true",
                    help="forward smoke mode to the experiments that "
                         f"support it ({', '.join(sorted(SMOKE_CAPABLE))})")
    args = ap.parse_args()
    enable_compile_cache()
    if args.only:
        names = [n.strip() for n in args.only.split(",") if n.strip()]
        unknown = [n for n in names if n not in EXPERIMENTS]
        if unknown:
            ap.error(f"unknown experiment(s) {', '.join(unknown)} "
                     f"(available: {', '.join(sorted(EXPERIMENTS))})")
    else:
        names = list(EXPERIMENTS)
    t0 = time.time()
    summary: Dict[str, Any] = {"smoke": args.smoke,
                               "experiments": {}}
    for name in names:
        print(f"\n=== {name} " + "=" * (70 - len(name)))
        if args.smoke and name in SMOKE_CAPABLE:
            result = EXPERIMENTS[name](smoke=True)
        else:
            result = EXPERIMENTS[name]()
        head = _headline(result)
        if head:
            summary["experiments"][name] = head
    summary["elapsed_s"] = round(time.time() - t0, 1)
    path = save_json("BENCH_10.json", summary)
    where = ("(smoke runs are assert-only: no new per-bench artifacts)"
             if args.smoke else "artifacts in benchmarks/results/")
    print(f"\nall benchmarks done in {summary['elapsed_s']:.0f}s; {where}")
    print(f"consolidated headline numbers -> {path}")


if __name__ == "__main__":
    main()

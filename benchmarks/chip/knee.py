#!/usr/bin/env python3
"""Find the knee of a serving cell: the highest open-loop rate the system
sustains without a growing backlog. One process builds and warms the cell
once, then runs a window at each rate in turn (draining in between) and
prints one JSON line per rate:

    python3 benchmarks/chip/knee.py --workload zamba2.chat \
        --rates 0.5 1 1.5 2 3 --seconds 30 --seed 1

A rate is sustained when every request sent in its window is served and
the backlog left at the close drains within twice the median time from a
request's due time to its last token. (Time to first token alone cannot
show a backlog: a prefilled request that waits for a decode row already
has its first token.) The knee is the highest rate sustained before the
first that is not. The cell then runs at a fixed fraction of the knee,
written into its traffic file as a number.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    spec = run.load_spec()
    cell_spec = run.find(spec["workloads"], args.workload, "workload")
    cfg = run.load_json(run.ROOT, run.find(spec["configs"],
                                           cell_spec["config"], "config")["file"])
    mix = run.load_json(HERE, "traffic", cell_spec["traffic"] + ".json")
    run.configure_jax(run.ROOT)
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("knee.py: needs a TPU")
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    import harness
    from harness import percentile

    cell = harness.Cell(cfg, dict(mix), args.seed, args.seconds, False)
    cell.build()
    cell.warm()
    sustained = None
    for rate in args.rates:
        cell.mix["rate_per_s"] = rate
        cell.served, cell.pumps, cell.compiles = [], [], []
        t = time.monotonic()
        cell.run_window()
        reqs = sorted(cell.served, key=lambda s: s.due_t)
        ttft = [s.stamps[0] - s.due_t if s.stamps else math.inf
                for s in reqs]
        e2e = [s.stamps[-1] - s.due_t if s.done else math.inf for s in reqs]
        drain = max((s.stamps[-1] for s in reqs if s.stamps),
                    default=cell.t_close) - cell.t_close
        unserved = sum(1 for s in reqs if not s.done)
        ok = unserved == 0 and drain <= 2 * percentile(e2e, 50)
        gaps = [b - a for s in reqs for a, b in zip(s.stamps, s.stamps[1:])]
        row = {"rate_per_s": rate, "requests": len(reqs),
               "unserved": unserved, "ttft_p50_s": percentile(ttft, 50),
               "ttft_p90_s": percentile(ttft, 90),
               "e2e_p50_s": percentile(e2e, 50), "drain_s": drain,
               "itl_p95_ms": percentile(gaps, 95) * 1e3 if gaps else None,
               "sustained": ok, "wall_s": time.monotonic() - t}
        print(json.dumps(row), flush=True)
        if ok:
            sustained = rate
        else:
            break
    print(json.dumps({"knee_rate_per_s": sustained}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

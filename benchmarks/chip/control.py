#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's numbers on several
seeds beside the control's, in one process.

    python3 benchmarks/chip/control.py --workload zamba2.chat \
        --seeds 1 2 3 --seconds 20

For each seed it runs the cell's window at the cell's own load and sizes,
then prints one JSON line with

  * ``program``: the numbers ``run.py`` compares (``run.compare``, with
    the configuration's limits) and the verdict ``correct`` it gives;
  * ``control``: the same for the float8 reference put in the program's
    place: at each served position of the same sample the gap of the
    token it ranks first; for training, plain AdamW whose forward pass
    reads float8 weights, against the float32 reference. A sound program
    reads ``correct`` true and the control false;
  * ``fault_half_batch`` (training): the reference fed half of each batch,
    the mean taken over the rest.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def verdict(compared: dict) -> dict:
    """The numbers beside their limits and the verdict ``run.py`` gives."""
    return {"correct": all(run._within(k, c) for k, c in compared.items()),
            "compared": compared}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    spec = run.load_spec()
    cell_spec = run.find(spec["workloads"], args.workload, "workload")
    cfg = run.load_json(run.ROOT, run.find(spec["configs"],
                                           cell_spec["config"], "config")["file"])
    mix = run.load_json(HERE, "traffic", cell_spec["traffic"] + ".json")
    run.configure_jax(run.ROOT)
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("control.py: needs a TPU")
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    import check
    import harness

    for seed in args.seeds:
        cell = harness.Cell(cfg, mix, seed, args.seconds, False)
        cell.build()
        cell.warm()
        cell.run_window()
        cell.free()
        if "train" in mix:
            harness.release()
        row = {"seed": seed, "requests": len(cell.served),
               "program": verdict(run.compare(cell, cfg, mix, seed)),
               "control": verdict(run.compare(cell, cfg, mix, seed,
                                              control=True))}
        if "train" in mix:
            first = next(j for j in cell.jobs if j.index == 0)
            ref = check.reference_train(cell, first.seed)
            half = mix["train"]["batch"] // 2
            row["fault_half_batch"] = check.train_gaps_between(
                ref, check.reference_train(cell, first.seed, rows=half))
        print(json.dumps(row), flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())

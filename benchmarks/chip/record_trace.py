#!/usr/bin/env python3
"""Record the small trace that ``test_trace.py`` checks the reduction on:
a few decode steps of the test-sized zamba2 layout on the chip, inside a
``bench.traced`` span with one ``bench.pump`` span per step.

    python3 benchmarks/chip/record_trace.py OUT.xplane.pb [--steps 4]

Prints the plane and line names it found and the number of steps, which
the test reads from ``testdata/decode_trace.json``.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out")
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import run
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    import harness
    import weights as W
    from repro.models import decode as D

    cfg = run.load_json(HERE, "testdata", "zamba2-tiny.json")
    pc = harness.program_config(cfg)
    params = W.make_params(cfg, 1)
    step = jax.jit(lambda p, c, t, pos: D.decode_step(p, pc, c, t, pos))
    cache = D.init_cache(pc, 2, 64)
    tok = jnp.zeros((2,), jnp.int32)
    pos = jnp.array([3, 5], jnp.int32)
    jax.block_until_ready(step(params, cache, tok, pos))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.traced"):
        for i in range(args.steps):
            with jax.profiler.TraceAnnotation("bench.pump"):
                lg, cache = step(params, cache, tok, pos + i)
                jax.block_until_ready(lg)
            with jax.profiler.TraceAnnotation("bench.wait"):
                jnp.zeros(()).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, args.out)
    shutil.rmtree(tmp)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(args.out)
    summary = {"steps": args.steps, "module_prefix": "jit__lambda",
               "planes": {p.name: [ln.name for ln in p.lines]
                          for p in pd.planes}}
    for p in pd.planes:
        for ln in p.lines:
            if ln.name in ("XLA Modules", "XLA Ops"):
                summary.setdefault("first_" + ln.name, [
                    e.name for e in list(ln.events)[:8]])
    print(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

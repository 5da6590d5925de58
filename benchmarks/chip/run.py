#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout. Everything else is found by name: the configuration under
``configs/<config>.json``, the traffic mix under ``traffic/<traffic>.json``
and each metric's reader under ``metrics/<metric>.py``. With ``--trace 0``
the result holds the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics (read from a run with the profiler on over the
window). The run fails, printing no result, when JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(items, name, what):
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def log(msg: str) -> None:
    print(f"[{time.time() - T_START:8.1f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_metrics(spec: dict, cell: str, trace: bool) -> list:
    """The metrics this cell reports: its end-to-end ones with --trace 0,
    its per-layer ones with --trace 1."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    sp = importlib.util.spec_from_file_location("metric_" + name.replace(
        ".", "_"), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def configure_jax(root: str) -> None:
    """The compile cache at one fixed path in the checkout (unless
    JAX_COMPILATION_CACHE_DIR names one), every program cached; libtpu's
    logs under TMPDIR; libtpu's pinned host staging buffer at 256 MiB.

    The staging buffer is mapped when the runtime starts: at its default
    size, on a host without transparent hugepages, the runtime's start
    took 5.4-9.6 s of a 14-19 s set-up and all of its spread (TPU v5e);
    at 256 MiB, 1.3-5.5 s. The window's transfers are a prompt's token ids
    and a step's few tokens, far under it."""
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(256 << 20))
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, require_tpu: bool = True) -> dict:
    """One run of ``workload`` of the BENCHMARK.json under ``root``;
    returns the result object."""
    spec = load_spec(root)
    cell_spec = find(spec["workloads"], workload, "workload")
    conf = find(spec["configs"], cell_spec["config"], "config")
    cfg = load_json(root, conf["file"])
    mix = load_json(root, os.path.relpath(HERE, ROOT), "traffic",
                    cell_spec["traffic"] + ".json")
    configure_jax(root)
    import jax
    events = _JaxEvents()
    log("jax imported")
    devs = jax.devices()
    log(f"devices: {len(devs)} {devs[0].platform}")
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < cell_spec["chips"]):
        raise SystemExit(f"run.py: {workload} needs {cell_spec['chips']} TPU "
                         f"chip(s); JAX found {len(devs)} {devs[0].platform} "
                         f"device(s) ({devs[0].device_kind})")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness
    import trace as TRC

    cell = harness.Cell(cfg, mix, seed, seconds, trace)
    cell.device_kind = devs[0].device_kind
    cell.build()
    log(f"built {workload} ({cfg['name']}) on {devs[0].device_kind}")
    cell.warm()
    cell.setup_s = time.time() - T_START
    events.active = False
    log(f"warmed: set-up {cell.setup_s:.1f}s")
    log("set-up phases: " + ", ".join(
        f"{name} {t - T_START:.2f}" for name, t in cell.marks))
    log(f"set-up jax events: {events.summary()}")
    cell.run_window()
    log(f"window closed: {len(cell.served)} requests, {len(cell.jobs)} "
        f"training jobs, {len(cell.pumps)} decode steps")
    # the readers see the window as it ran: records are taken before the
    # program's state is freed
    recs = {}
    for h in cell.cluster.handles:
        if h.job.name.startswith("prefill/") and h.records:
            recs[int(h.job.name.split("/")[1])] = h.records[-1]
    cell.prefill_records = [recs.get(s.sr.rid) for s in cell.served]
    cell.free()
    values = {}
    for m in cell_metrics(spec, workload, trace):
        v = reader(m["name"])(cell)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # correctness, once the window is over and the program's state is gone
    failed = [s for s in cell.served if not s.done
              or len(s.sr.tokens) != s.gen_len]
    if "train" in mix:
        harness.release()
    compared = compare(cell, cfg, mix, seed)
    correct = all(_within(k, c) for k, c in compared.items())
    log(f"checked: memory peak {cell.mem['peak_bytes_in_use'] / 1e9:.3f} GB "
        f"of {cell.mem['bytes_limit'] / 1e9:.3f}, reserved peak "
        f"{cell.mem['reserved_peak'] / 1e9:.3f} GB")

    out = {"correct": correct,
           "attempted": len(cell.served) + len(cell.jobs),
           "failed": len(failed) + sum(1 for j in cell.jobs
                                       if j.error or not _job_done(j)),
           "metrics": values,
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind,
                      "count": len(devs),
                      "memory_peak_bytes": cell.mem["peak_bytes_in_use"]}}
    if trace and cell.trace_data is not None:
        out["device"]["busy_s"] = TRC.busy_s(cell.trace_data)
        out["device"]["window_s"] = TRC.window_s(cell.trace_data)
        out["breakdown"] = {"device_ops": TRC.top_ops(cell.trace_data),
                            "idle_gaps": TRC.idle_gaps(cell.trace_data)}
    out["compared"] = compared
    return out


def compare(cell, cfg: dict, mix: dict, seed: int,
            control: bool = False) -> dict:
    """Each number that decides ``correct``, beside its limit from the
    configuration file. ``control``: the float8 reference stands in for the
    program (its tokens for the served ones; for training, plain AdamW
    whose forward pass reads float8 weights)."""
    import check
    lim = cfg["limits"]
    failed = [s for s in cell.served if not s.done
              or len(s.sr.tokens) != s.gen_len]
    g = check.served_gaps(cfg, mix, seed, cell.served, control=control)
    compared = {"requests_not_served": {"value": len(failed), "limit": 0},
                "widest_logit_gap": {"value": g["widest_logit_gap"],
                                     "limit": lim["widest_logit_gap"]},
                "checked_tokens": {"value": g["checked_tokens"], "limit": 1}}
    if "train" in mix:
        bad = [j for j in cell.jobs if j.error or not _job_done(j)]
        compared["train_jobs_failed"] = {"value": len(bad), "limit": 0}
        first = next((j for j in cell.jobs if j.index == 0), None)
        if first is not None and first.update_norms is not None:
            if control:
                ref = check.reference_train(cell, first.seed)
                gaps = check.train_gaps_between(
                    ref, check.reference_train(cell, first.seed, quant=True))
            else:
                gaps = check.train_gaps(cell, first)
            for k, v in gaps.items():
                compared[k] = {"value": v, "limit": lim[k]}
        else:
            compared["train_first_job_checked"] = {"value": 0, "limit": 1}
    return compared


class _JaxEvents:
    """Counts of JAX's compile-cache events and sums of its compile-phase
    durations while ``active`` (set-up), for the log."""

    def __init__(self):
        import jax
        self.active = True
        self.counts: dict = {}
        self.secs: dict = {}

        def event(name, **_):
            if self.active:
                self.counts[name] = self.counts.get(name, 0) + 1

        def duration(name, secs, **_):
            if self.active:
                self.secs[name] = self.secs.get(name, 0.0) + secs
        jax.monitoring.register_event_listener(event)
        jax.monitoring.register_event_duration_secs_listener(duration)

    def summary(self) -> str:
        short = lambda k: k.rsplit("/", 1)[-1]  # noqa: E731
        return ", ".join([f"{short(k)} {v}" for k, v in sorted(
            self.counts.items())] + [f"{short(k)} {v:.2f}s" for k, v in sorted(
                self.secs.items())])


def _job_done(job) -> bool:
    return job.handle.status.value == "done"


def _within(name: str, c: dict) -> bool:
    """Counts that must reach a floor (``checked_tokens``,
    ``train_first_job_checked``) against the others' ceilings."""
    v = c["value"]
    if not math.isfinite(v):
        return False
    if name in ("checked_tokens", "train_first_job_checked"):
        return v >= c["limit"]
    return v <= c["limit"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    res = run(args.workload, args.seed, args.seconds, bool(args.trace))
    for k, c in res["compared"].items():
        print(f"{k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

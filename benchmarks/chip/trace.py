"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the newest ``*.xplane.pb`` under a profiler directory (with
nothing but ``jax.profiler.ProfileData``) into a plain dict:

    {"window": [start_ns, end_ns],          # the "bench.traced" host span
     "host": [[name, start_ns, end_ns], ...],   # the harness's bench.* spans
     "devices": {plane: {"ops": [[name, start, end], ...],
                         "modules": [[name, start, end], ...]}}}

and the functions below reduce that dict. Device and host events share
the profiler's clock. ``test_trace.py`` checks every reduction against a
brute-force count on a small trace recorded on a TPU v5e
(``testdata/decode_trace.xplane.pb``).
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.traced"


def load(path: str) -> dict:
    """The trace under ``path`` (a profiler directory or an .xplane.pb)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = max(files, key=os.path.getmtime)
    pd = ProfileData.from_file(path)
    host, devices, window = [], {}, None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            d = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    d[key] = [[e.name, e.start_ns, e.end_ns]
                              for e in line.events]
            if d["ops"] or d["modules"]:
                devices[plane.name] = d
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = [e.start_ns, e.end_ns]
                    elif e.name.startswith("bench."):
                        host.append([e.name, e.start_ns, e.end_ns])
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span in the trace")
    return {"window": window, "host": sorted(host, key=lambda e: e[1]),
            "devices": devices}


def _clip(events, lo, hi) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for _, s, e in events
            if e > lo and s < hi]


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window_s(tr: dict) -> float:
    lo, hi = tr["window"]
    return (hi - lo) * 1e-9


def _busy_intervals(dev: dict, lo, hi):
    return _union(_clip(dev["ops"] or dev["modules"], lo, hi))


def busy_s(tr: dict) -> float:
    """Seconds in the window in which an operation ran, averaged over the
    devices in the trace."""
    lo, hi = tr["window"]
    devs = list(tr["devices"].values())
    if not devs:
        return 0.0
    return sum(sum(e - s for s, e in _busy_intervals(d, lo, hi))
               for d in devs) * 1e-9 / len(devs)


def module_s(tr: dict, prefix: str) -> Tuple[float, int]:
    """(device seconds, count) of the modules whose name starts with
    ``prefix`` (the parts inside the window), summed over devices."""
    lo, hi = tr["window"]
    tot, n = 0.0, 0
    for d in tr["devices"].values():
        for name, s, e in d["modules"]:
            if name.startswith(prefix) and e > lo and s < hi:
                tot += min(e, hi) - max(s, lo)
                n += 1
    return tot * 1e-9, n


def op_name(event_name: str) -> str:
    """An XLA op's instruction name from its trace name, which on a TPU is
    the whole HLO line ("%fusion.12 = bf16[...] fusion(...), ...")."""
    head = event_name.split(" = ", 1)[0]
    return head.lstrip("%")[:80]


def top_ops(tr: dict, k: int = 10) -> List[list]:
    """The ``k`` operations that took most device time in the window:
    [[name, seconds], ...] (per device average)."""
    lo, hi = tr["window"]
    acc: Dict[str, float] = {}
    devs = list(tr["devices"].values())
    for d in devs:
        for name, s, e in d["ops"]:
            if e > lo and s < hi:
                n = op_name(name)
                acc[n] = acc.get(n, 0.0) + (min(e, hi) - max(s, lo))
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v * 1e-9 / max(len(devs), 1)] for n, v in top]


def host_label(tr: dict, t: float) -> str:
    """The innermost bench.* host span around time ``t`` ("host-other" when
    the harness was in none)."""
    best: Optional[list] = None
    for name, s, e in tr["host"]:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = [name, s, e]
    return best[0] if best else "host-other"


def idle_gaps(tr: dict, k: int = 10) -> List[list]:
    """The ``k`` longest stretches of the window in which the first device
    ran nothing, each named by what the host harness was doing at its
    middle: [[label, seconds], ...]."""
    lo, hi = tr["window"]
    if not tr["devices"]:
        return []
    busy = _busy_intervals(next(iter(tr["devices"].values())), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[host_label(tr, (s + e) / 2), (e - s) * 1e-9]
            for s, e in gaps[:k]]

"""The trace reduction against a brute-force count on a small trace
recorded on a TPU v5e by ``record_trace.py`` (four decode steps of the
test-size zamba2 layout, each inside a ``bench.pump`` span).

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/test_trace.py
"""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import trace as TRC  # noqa: E402

PB = os.path.join(HERE, "testdata", "decode_trace.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "testdata", "decode_trace.json")) as f:
        meta = json.load(f)
    return TRC.load(PB), meta


def _covered_ns(intervals, lo, hi, step=1000):
    """Brute force: microsecond ticks in [lo, hi) inside some interval."""
    n = 0
    for t in range(int(lo), int(hi), step):
        if any(s <= t < e for s, e in intervals):
            n += 1
    return n * step


def test_window_and_device_found(recorded):
    tr, _ = recorded
    assert tr["devices"], "no device plane with XLA ops in the trace"
    assert 0 < TRC.window_s(tr) < 5
    assert all(name.startswith("bench.") for name, _, _ in tr["host"])


def test_busy_matches_brute_force(recorded):
    tr, _ = recorded
    lo, hi = tr["window"]
    dev = next(iter(tr["devices"].values()))
    iv = [(s, e) for _, s, e in dev["ops"] or dev["modules"]]
    brute = _covered_ns(iv, lo, hi) * 1e-9
    busy = TRC.busy_s(tr)
    assert 0 < busy <= TRC.window_s(tr)
    assert abs(busy - brute) <= 2e-6 * max(len(iv), 1) + 1e-5


def test_one_decode_module_per_step(recorded):
    tr, meta = recorded
    secs, n = TRC.module_s(tr, meta["module_prefix"])
    assert n == meta["steps"]
    assert 0 < secs <= TRC.busy_s(tr) * len(tr["devices"]) + 1e-6


def test_idle_gaps_fill_the_rest(recorded):
    tr, _ = recorded
    gaps = TRC.idle_gaps(tr, k=10**6)
    idle = sum(g for _, g in gaps)
    assert abs(idle + TRC.busy_s(tr) - TRC.window_s(tr)) < 1e-6
    labels = {name for name, _ in gaps}
    assert labels <= {"bench.pump", "bench.wait", "bench.traced",
                      "host-other"}


def test_top_ops_within_busy(recorded):
    tr, _ = recorded
    top = TRC.top_ops(tr, k=10**6)
    assert top and all(v > 0 for _, v in top)
    assert sum(v for _, v in top) >= TRC.busy_s(tr) - 1e-6

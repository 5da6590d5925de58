"""The one traffic generator. A mix is a data file under ``traffic/``;
this module turns it and a seed into a schedule.

Every seed gets the same multiset of sizes and of gaps between arrivals,
drawn as evenly spaced quantiles of the mix's distributions, in an order
the seed shuffles; only the order and the prompts' token ids change with
the seed. So seeds differ in how work interleaves, not in how much work
there is.

Mix keys:
  ``arrivals``   "open": arrivals at ``rate_per_s``, with exponential gaps;
  ``prompt``, ``output``: a length distribution
                 {"dist": "lognormal", "median", "sigma", "min", "max"};
                 ``prompt.buckets`` rounds prompt lengths up to the
                 listed lengths;
  ``rows``, ``max_seq``: decode rows per loop and the cache length;
  ``check_requests``: how many finished requests the output check reads;
  ``train`` (optional): a closed loop of training jobs beside serving,
                 {"concurrency", "steps", "batch", "seq", "layers",
                  "priority", "optimizer": {...}}.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    due: float              # seconds after the window opens
    prompt_len: int
    gen_len: int            # output tokens, the first included
    prompt: np.ndarray      # [prompt_len] int32 token ids


def _quantiles(dist: dict, n: int) -> List[int]:
    """``n`` evenly spaced quantiles of a length distribution."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist(math.log(dist["median"]), dist["sigma"])
    out = [int(round(math.exp(nd.inv_cdf((i + 0.5) / n)))) for i in range(n)]
    lo, hi = dist.get("min", 1), dist.get("max", 1 << 30)
    out = [min(max(x, lo), hi) for x in out]
    buckets = dist.get("buckets")
    if buckets:
        out = [min(b for b in buckets if b >= x) for x in out]
    return out


def prompt_lengths(mix: dict) -> List[int]:
    """Every prompt length the mix can send (the shapes to warm up)."""
    if not mix["prompt"].get("buckets"):
        raise ValueError("a mix must round its prompt lengths to buckets, "
                         "or every new length compiles")
    return sorted(mix["prompt"]["buckets"])


def schedule(mix: dict, seed: int, seconds: float,
             vocab: int) -> List[Request]:
    """The requests of one run: ``rate_per_s * seconds`` arrivals whose
    gaps are exponential quantiles in a seeded order."""
    if mix["arrivals"] != "open":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    rng = np.random.default_rng([int(seed) % (1 << 63), 7])
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / mix["rate_per_s"]
            for i in range(n)]
    gaps = [gaps[i] for i in rng.permutation(n)]
    dues = list(np.cumsum(gaps) - gaps[0])
    plens = [_quantiles(mix["prompt"], n)[i] for i in rng.permutation(n)]
    glens = [_quantiles(mix["output"], n)[i] for i in rng.permutation(n)]
    return [Request(i, float(dues[i]), plens[i], glens[i],
                    rng.integers(0, vocab, plens[i], dtype=np.int32))
            for i in range(n)]


def check_sample(done: List[Request], mix: dict, seed: int) -> List[int]:
    """Indices (into ``done``) of the requests the output check reads: the
    longest one, and the rest drawn from the seed."""
    k = min(mix["check_requests"], len(done))
    if k == 0:
        return []
    longest = max(range(len(done)),
                  key=lambda i: done[i].prompt_len + done[i].gen_len)
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([int(seed) % (1 << 63), 11])
    pick = rng.choice(len(rest), size=k - 1, replace=False) if k > 1 else []
    return [longest] + [rest[i] for i in pick]

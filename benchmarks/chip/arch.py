"""What the benchmark knows of one program architecture, found by name.

A configuration file states the program's architecture it runs
(``program_arch``); ``archs/<program_arch>.py`` holds everything of that
architecture the benchmark needs, so a new architecture is a new file
and no edit to the generic ones:

  * ``layout(cfg)``: every weight as ``{path tuple: (shape, dtype name,
    init)}``, in the program's parameter tree (``weights.py`` seeds it);
  * ``hidden(params, cfg, tokens, quant)``: the float32 reference from the
    embedding to the final norm, [B, S] -> [B, S, d], built from the
    blocks of ``reference.py`` (which puts the output head on it);
  * ``decode(cfg, rows, kv_positions)`` and ``prefill(cfg, length)``:
    (FLOPs, bytes) of a step, and ``train_flops_per_token(cfg, seq)``, by
    the conventions of ``counts.py``;
  * ``program_fields(cfg, base)``: the program's ``ArchConfig`` fields that
    the file sets beyond the shared sizes, from the registered ``base``
    (``harness.program_config`` holds them to ``reduced`` alike).
"""
from __future__ import annotations

import functools
import importlib.util
import os
import re

ARCHS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "archs")


def module(cfg: dict):
    """The architecture module of the configuration ``cfg``."""
    return load(os.path.join(ARCHS, cfg["program_arch"] + ".py"))


@functools.lru_cache(maxsize=None)
def load(path: str):
    """The Python file at ``path`` as a module, loaded once (architectures
    here, metric readers in ``run.py``)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no such file: {path}")
    name = "bench_" + re.sub(r"\W", "_", os.path.basename(path)[:-3])
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

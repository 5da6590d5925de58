"""Operations and bytes that the algorithm needs, from a configuration's
shapes. Each architecture's file (``archs/<program_arch>.py``) counts its
own by these conventions; they are the same whatever implements them:

  * every weight is read once per step (a block shared between layers
    too, though it is applied in each), and of the embedding only the rows
    looked up;
  * each row's recurrent state (SSM and convolution) is read and written
    once per layer;
  * attention reads the cached keys and values of the positions a row
    attends to and writes one new entry per row.

Left out on purpose: a rewrite of the whole cache, recompute under remat,
padding to a bucket or to the loop's idle rows. A later fix that stops
doing such work raises a roofline share instead of leaving these counts
stale. A multiply-add counts as two operations.
"""
from __future__ import annotations

import arch

BF16, F32 = 2, 4


def decode(cfg: dict, rows: int, kv_positions: int):
    """(FLOPs, bytes) of one decode step for ``rows`` rows that attend to
    ``kv_positions`` cached positions between them (new entries included)."""
    return arch.module(cfg).decode(cfg, rows, kv_positions)


def prefill(cfg: dict, length: int):
    """(FLOPs, bytes) of one prompt of ``length`` tokens: causal attention
    over half the square, and the prompt's cache written once."""
    return arch.module(cfg).prefill(cfg, length)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward operations per trained token (three times the
    forward pass, no recompute) at sequence length ``seq``."""
    return arch.module(cfg).train_flops_per_token(cfg, seq)

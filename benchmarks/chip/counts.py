"""Operations and bytes that the algorithm needs, from the shapes of a
hybrid (Mamba-2 + shared attention) configuration. They are the same
whatever implements them:

  * every weight is read once per step (the shared block of a hybrid too,
    though it is applied in every group), and of the embedding only the
    rows looked up;
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

import weights as W

BF16, F32 = 2, 4


def _dims(cfg: dict) -> dict:
    if cfg["family"] != "hybrid":
        raise ValueError(f"no counts for family {cfg['family']!r}")
    d, ssm = cfg["d_model"], cfg["ssm"]
    e, k = ssm["expand"] * d, cfg["hybrid_shared_every"]
    return {"d": d, "v": cfg["vocab"], "e": e, "n": ssm["state_dim"],
            "w": ssm["conv_width"], "groups": cfg["n_layers"] // k,
            "ssm_layers": cfg["n_layers"] // k * (k - 1),
            "nh": e // ssm["headdim"], "h": cfg["n_heads"],
            "kvh": cfg["n_kv_heads"], "hd": cfg["head_dim"], "f": cfg["d_ff"]}


def matmul_params_per_token(cfg: dict) -> int:
    """Weights multiplied once per token (the shared block once per group),
    the output head included and the embedding lookup not."""
    x = _dims(cfg)
    d, e, n, v = x["d"], x["e"], x["n"], x["v"]
    mamba = d * (2 * e + 2 * n + x["nh"]) + e * d
    shared = d * x["hd"] * (2 * x["h"] + 2 * x["kvh"]) + 3 * d * x["f"]
    return x["ssm_layers"] * mamba + x["groups"] * shared + d * v


def _ssm_flops_per_token(cfg: dict) -> int:
    """The recurrence, the causal convolution and the gating per token."""
    x = _dims(cfg)
    state = x["nh"] * cfg["ssm"]["headdim"] * x["n"]
    conv = (x["e"] + 2 * x["n"]) * x["w"]
    return x["ssm_layers"] * (5 * state + 2 * conv)


def _attn_flops(cfg: dict, positions: int) -> int:
    """Scores and weighted sum over ``positions`` attended keys, summed
    over the attention applications of the model."""
    x = _dims(cfg)
    return x["groups"] * 4 * x["h"] * x["hd"] * positions


def state_bytes_per_row(cfg: dict) -> int:
    """One row's recurrent state (read or written once)."""
    x = _dims(cfg)
    ssm = x["nh"] * cfg["ssm"]["headdim"] * x["n"] * F32
    conv = (x["w"] - 1) * (x["e"] + 2 * x["n"]) * BF16
    return x["ssm_layers"] * (ssm + conv)


def kv_bytes_per_position(cfg: dict) -> int:
    """Keys and values of one position over every attention application."""
    x = _dims(cfg)
    return x["groups"] * 2 * x["kvh"] * x["hd"] * BF16


def step_weight_bytes(cfg: dict, rows: int) -> int:
    x = _dims(cfg)
    embed = x["v"] * x["d"] * BF16
    return W.param_bytes(cfg) - embed + rows * x["d"] * BF16


def decode(cfg: dict, rows: int, kv_positions: int):
    """(FLOPs, bytes) of one decode step for ``rows`` rows that attend to
    ``kv_positions`` cached positions between them (new entries included)."""
    flops = rows * (2 * matmul_params_per_token(cfg)
                    + _ssm_flops_per_token(cfg)) \
        + _attn_flops(cfg, kv_positions)
    nbytes = step_weight_bytes(cfg, rows) \
        + 2 * rows * state_bytes_per_row(cfg) \
        + kv_bytes_per_position(cfg) * (kv_positions + rows)
    return float(flops), float(nbytes)


def prefill(cfg: dict, length: int):
    """(FLOPs, bytes) of one prompt of ``length`` tokens: causal attention
    over half the square, and the prompt's cache written once."""
    flops = length * (2 * matmul_params_per_token(cfg)
                      + _ssm_flops_per_token(cfg)) \
        + _attn_flops(cfg, length * (length + 1) // 2)
    nbytes = step_weight_bytes(cfg, length) + state_bytes_per_row(cfg) \
        + kv_bytes_per_position(cfg) * length
    return float(flops), float(nbytes)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward operations per trained token (three times the
    forward pass, no recompute) at sequence length ``seq``."""
    fwd = 2 * matmul_params_per_token(cfg) + _ssm_flops_per_token(cfg) \
        + _attn_flops(cfg, (seq + 1) // 2)
    return 3.0 * fwd

"""The program's zamba2-2.7b: ``n_layers / k`` groups (``k`` is
``hybrid_shared_every``), each of ``k - 1`` Mamba-2 layers followed by
the one attention + gated MLP block that every group shares, with a norm
of its own before each use. Weights of the Mamba-2 layers are stacked on
(group, layer) axes, the shared block's are held once.

These are the program's widths, not the published model's: attention is
32 x 80 over d_model where Zamba2-2.7B has 32 x 160, and one shared block
where it has two (the configuration's ``departures``).
"""
from __future__ import annotations

import jax

import counts as C
import reference as R
import weights as W


def layout(cfg: dict) -> dict:
    """{path tuple: (shape, dtype name, init)} of every weight. ``init`` is
    ("normal", std) | ("const", value) | ("a_log", lo, hi) | ("dt_bias",)."""
    d, v = cfg["d_model"], cfg["vocab"]
    wd = cfg["dtype"]
    out = {("embed",): ((v, d), wd, ("normal", 0.02))}
    ssm = cfg["ssm"]
    k = cfg["hybrid_shared_every"]
    g = cfg["n_layers"] // k
    st = (g, k - 1)
    e, n = ssm["expand"] * d, ssm["state_dim"]
    nh, w = e // ssm["headdim"], ssm["conv_width"]
    m = ("groups", "mamba")
    out.update({
        m + ("in_proj",): (st + (d, 2 * e + 2 * n + nh), wd,
                           ("normal", d ** -0.5)),
        m + ("conv_w",): (st + (e + 2 * n, w), wd, ("normal", 0.2)),
        m + ("conv_b",): (st + (e + 2 * n,), wd, ("normal", 0.02)),
        m + ("dt_bias",): (st + (nh,), "float32", ("dt_bias",)),
        m + ("A_log",): (st + (nh,), "float32", ("a_log", 1.0, 16.0)),
        m + ("D",): (st + (nh,), "float32", ("const", 1.0)),
        m + ("norm",): (st + (e,), wd, ("normal", 0.05)),
        m + ("out_proj",): (st + (e, d), wd, ("normal", e ** -0.5)),
        ("groups", "norm_m"): (st + (d,), wd, ("normal", 0.05)),
        ("groups", "norm_attn"): ((g, d), wd, ("normal", 0.05)),
        ("groups", "norm_mlp"): ((g, d), wd, ("normal", 0.05)),
    })
    h, kv, hd, f = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"], \
        cfg["d_ff"]
    a = ("shared", "attn")
    out.update({
        a + ("wq",): ((d, h, hd), wd, ("normal", d ** -0.5)),
        a + ("wk",): ((d, kv, hd), wd, ("normal", d ** -0.5)),
        a + ("wv",): ((d, kv, hd), wd, ("normal", d ** -0.5)),
        a + ("wo",): ((h, hd, d), wd, ("normal", (h * hd) ** -0.5)),
        ("shared", "mlp", "wi"): ((d, f), wd, ("normal", d ** -0.5)),
        ("shared", "mlp", "wg"): ((d, f), wd, ("normal", d ** -0.5)),
        ("shared", "mlp", "wo"): ((f, d), wd, ("normal", f ** -0.5)),
    })
    out[("final_norm",)] = ((d,), wd, ("normal", 0.05))
    out[("lm_head",)] = ((d, v), wd, ("normal", d ** -0.5))
    return out


def hidden(params, cfg, tokens, quant=False):
    """Final-normed hidden states [B, S, d] for ``tokens`` [B, S]."""
    eps = cfg["norm_eps"]
    x = params["embed"][tokens].astype(R.F32)
    shared = params["shared"]

    def group(h, gp):
        def layer(hh, mp):
            return hh + R.mamba2(mp["mamba"], R.rms_norm(hh, mp["norm_m"], eps),
                                 cfg["ssm"], eps, quant), None
        h, _ = jax.lax.scan(layer, h, {"mamba": gp["mamba"],
                                      "norm_m": gp["norm_m"]})
        h = h + R.attention(shared["attn"],
                            R.rms_norm(h, gp["norm_attn"], eps), cfg, quant)
        h = h + R.gated_mlp(shared["mlp"], R.rms_norm(h, gp["norm_mlp"], eps),
                            quant)
        return h, None
    x, _ = jax.lax.scan(group, x, params["groups"])
    return R.rms_norm(x, params["final_norm"], eps)


def program_fields(cfg: dict, base) -> dict:
    """The group length and the Mamba-2 sizes."""
    from repro.configs.base import SSMConfig
    s = cfg["ssm"]
    return {"hybrid_shared_every": cfg["hybrid_shared_every"],
            "ssm": SSMConfig(state_dim=s["state_dim"],
                             conv_width=s["conv_width"], expand=s["expand"],
                             headdim=s.get("headdim", base.ssm.headdim),
                             chunk=s.get("chunk", base.ssm.chunk))}


# -- counts (conventions in counts.py) ----------------------------------------

def _dims(cfg: dict) -> dict:
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
    ssm = x["nh"] * cfg["ssm"]["headdim"] * x["n"] * C.F32
    conv = (x["w"] - 1) * (x["e"] + 2 * x["n"]) * C.BF16
    return x["ssm_layers"] * (ssm + conv)


def kv_bytes_per_position(cfg: dict) -> int:
    """Keys and values of one position over every attention application."""
    x = _dims(cfg)
    return x["groups"] * 2 * x["kvh"] * x["hd"] * C.BF16


def step_weight_bytes(cfg: dict, rows: int) -> int:
    x = _dims(cfg)
    embed = x["v"] * x["d"] * C.BF16
    return W.param_bytes(cfg) - embed + rows * x["d"] * C.BF16


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

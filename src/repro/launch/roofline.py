"""Three-term roofline analysis from compiled XLA artifacts (no hardware).

Per (arch x shape x mesh):
    compute_s    = per-device HLO FLOPs / peak_FLOPs_per_chip
    memory_s     = per-device HLO bytes / HBM bandwidth
    collective_s = per-device collective link bytes / ICI link bandwidth

``cost_analysis()`` of the SPMD-partitioned executable reports PER-DEVICE
flops/bytes (the module is the per-device program), so each term divides by a
single chip's peak — mathematically identical to global/(chips*peak).

collective bytes are parsed from ``compiled.as_text()``: for each collective
op we sum the shape literals on the defining line (operands + result) and
apply a traffic factor (all-reduce: 1.0 of op+res ~= 2S ring traffic;
all-gather/reduce-scatter: 1.0 ~= S; all-to-all/collective-permute: 0.5).
This is napkin-accurate ring accounting, documented in EXPERIMENTS.md.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

# the dry-run compiles for a v5e production mesh: its roofline is the v5e's
# whatever device this process sees
from repro.core.chips import V5E


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(pred|[suf]\d+|bf16|c64|c128)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s+[^=]*\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")

_FACTOR = {"all-reduce": 1.0, "all-gather": 1.0, "reduce-scatter": 1.0,
           "all-to-all": 0.5, "collective-permute": 0.5}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device collective traffic bytes by op kind, from partitioned HLO."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        total = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(line))
        out[kind] = out.get(kind, 0.0) + total * _FACTOR[kind]
    return out


@dataclasses.dataclass
class Roofline:
    hlo_flops_per_device: float  # raw cost_analysis (while bodies counted ONCE)
    analytic_flops_global: float  # repro.launch.flops — the real compute term
    bytes_per_device: float
    collective_per_device: float
    coll_breakdown: Dict[str, float]
    peak_mem_per_device: float
    chips: int
    model_flops: float           # 6*N_active*tokens (train) / 2*N_active*tokens
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    dominant: str = ""
    useful_ratio: float = 0.0    # MODEL_FLOPS / analytic compiled FLOPs
    roofline_fraction: float = 0.0  # useful compute time / max(term)

    def finalize(self) -> "Roofline":
        self.compute_s = self.analytic_flops_global / (self.chips * V5E.flops)
        self.memory_s = self.bytes_per_device / V5E.hbm_bw
        self.collective_s = self.collective_per_device / V5E.ici_bw
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        self.dominant = max(terms, key=terms.get)
        self.useful_ratio = (self.model_flops / self.analytic_flops_global
                             if self.analytic_flops_global else 0.0)
        # fraction of roofline: time the USEFUL model flops would take at peak
        # vs. the bounding term of the compiled program
        useful_s = self.model_flops / (self.chips * V5E.flops)
        bound = max(terms.values())
        self.roofline_fraction = useful_s / bound if bound else 0.0
        return self

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def model_flops(cfg, shape) -> float:
    """Useful model FLOPs: 6*N_active*D (train), 2*N_active*D (inference)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch  # decode: one token/seq


def analyze(compiled, cfg, shape, chips: int,
            hlo_text: Optional[str] = None) -> Roofline:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older jax returns [dict]
        cost = cost[0]
    mem = compiled.memory_analysis()
    peak = (getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text)
    from repro.launch.flops import step_flops
    return Roofline(
        hlo_flops_per_device=float(cost.get("flops", 0.0)),
        analytic_flops_global=step_flops(cfg, shape),
        bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        collective_per_device=float(sum(coll.values())),
        coll_breakdown=coll,
        peak_mem_per_device=float(peak),
        chips=chips,
        model_flops=model_flops(cfg, shape),
    ).finalize()

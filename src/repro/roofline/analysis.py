"""Three-term roofline analysis from the compiled dry-run artifact.

    compute term    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory term     = HLO_bytes / (chips × HBM_bw)
    collective term = collective_bytes / (chips × link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``.
collective_bytes is parsed from the HLO text: the summed operand sizes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (the prompt-specified convention).

``model_flops`` computes the useful-compute yardstick 6·N·D (train, dense)
or 6·N_active·D (MoE); the ratio MODEL_FLOPS / HLO_FLOPs exposes remat and
dispatch waste.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

import jax
import numpy as np

from repro.launch.mesh import HBM_BW, ICI_BW_PER_LINK, PEAK_FLOPS_BF16
from repro.models.config import ModelConfig
from repro.models.spec import is_def

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# e.g. "bf16[256,4096,7168]{2,1,0}" — captures dtype + dims
_TYPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _type_bytes(dtype: str, dims: str) -> int:
    nb = _DTYPE_BYTES.get(dtype)
    if nb is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * nb


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum operand sizes per collective kind over the HLO module text."""
    out = {k: 0 for k in _COLLECTIVES}
    out["total"] = 0
    for line in hlo_text.splitlines():
        # match "= <type> <op-name>(" — the op must be the instruction,
        # not a substring of a metadata field
        m = re.search(r"=\s+[\w\[\],{}() ]*?\s(" + "|".join(_COLLECTIVES)
                      + r")(?:-start|-done)?\(", line)
        if not m:
            continue
        kind = m.group(1)
        # operand types appear inside the call parentheses
        call = line[m.end() - 1:]
        nbytes = 0
        for tm in _TYPE_RE.finditer(call):
            nbytes += _type_bytes(tm.group(1), tm.group(2))
        out[kind] += nbytes
        out["total"] += nbytes
    return out


def hlo_cost(compiled: Any) -> dict[str, float]:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    byac = float(ca.get("bytes accessed", 0.0))
    return {"flops": flops, "bytes": byac}


def active_param_count(cfg: ModelConfig, defs: Any) -> tuple[int, int]:
    """(total_params, active_params): routed experts count as top_k/E."""
    total = 0
    active = 0.0
    for path, d in jax.tree.flatten_with_path(defs, is_leaf=is_def)[0]:
        n = int(np.prod(d.shape)) if d.shape else 1
        total += n
        if cfg.moe and "experts" in d.axes:
            active += n * (cfg.moe.top_k / cfg.moe.n_experts)
        else:
            active += n
    return total, int(active)


def model_flops(cfg: ModelConfig, defs: Any, *, kind: str, tokens: int) -> float:
    """6·N_active·D for training, 2·N_active·D for inference."""
    _, active = active_param_count(cfg, defs)
    mult = 6.0 if kind == "train" else 2.0
    return mult * active * tokens


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_breakdown: dict[str, int]
    model_flops: float
    per_device_hbm_bytes: float = 0.0
    # raw per-instruction surface traffic (CPU-module, fusion-naive) — the
    # memory term uses the TPU-fusion-adjusted hlo_bytes instead
    hlo_bytes_raw: float = 0.0
    # surface of score-dominated attention dots (VMEM-resident under the
    # Pallas flash kernel; memory_kernel_s subtracts it)
    attn_score_bytes: float = 0.0
    xla_reported_flops: float = 0.0   # raw HloCostAnalysis (while-body-once)
    xla_reported_bytes: float = 0.0

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / (self.chips * PEAK_FLOPS_BF16)

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / (self.chips * HBM_BW)

    @property
    def memory_kernel_s(self) -> float:
        """Memory term with the flash-attention kernel deployed (score
        tiles stay in VMEM; conservative — softmax reduce traffic on the
        tiles is still counted)."""
        return max(self.hlo_bytes - self.attn_score_bytes, 0.0) / (
            self.chips * HBM_BW)

    @property
    def collective_s(self) -> float:
        return self.coll_bytes / (self.chips * ICI_BW_PER_LINK)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful compute / achievable time: MODEL_FLOPS / (chips·peak·T_bound)
        where T_bound = max of the three terms (the bound on step time)."""
        t = max(self.compute_s, self.memory_s, self.collective_s)
        if t <= 0:
            return 0.0
        return self.model_flops / (self.chips * PEAK_FLOPS_BF16 * t)

    @property
    def roofline_fraction_kernel(self) -> float:
        """Roofline fraction with the Pallas flash-attention kernel's
        VMEM-resident score tiles subtracted from the memory term."""
        t = max(self.compute_s, self.memory_kernel_s, self.collective_s)
        if t <= 0:
            return 0.0
        return self.model_flops / (self.chips * PEAK_FLOPS_BF16 * t)

    def row(self) -> dict[str, Any]:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops": f"{self.hlo_flops:.3e}",
            "hlo_bytes": f"{self.hlo_bytes:.3e}",
            "hlo_bytes_raw": f"{self.hlo_bytes_raw:.3e}",
            "coll_bytes": f"{self.coll_bytes:.3e}",
            "compute_s": round(self.compute_s, 6),
            "memory_s": round(self.memory_s, 6),
            "memory_kernel_s": round(self.memory_kernel_s, 6),
            "collective_s": round(self.collective_s, 6),
            "dominant": self.dominant,
            "model_flops": f"{self.model_flops:.3e}",
            "useful_ratio": round(self.useful_ratio, 4),
            "roofline_fraction": round(self.roofline_fraction, 4),
            "roofline_fraction_kernel": round(self.roofline_fraction_kernel, 4),
            "per_device_hbm_gb": round(self.per_device_hbm_bytes / 2**30, 3),
        }


def analyze(*, arch: str, shape: str, mesh_name: str, chips: int,
            compiled: Any, hlo_text: str, cfg: ModelConfig, defs: Any,
            kind: str, tokens: int,
            per_device_hbm_bytes: float = 0.0) -> RooflineReport:
    """All reported quantities are GLOBAL (per-device HLO costs × chips).

    FLOPs/bytes/collective bytes come from the trip-count-aware HLO
    roll-up (``hlo_cost.analyze_hlo``) because XLA's HloCostAnalysis counts
    while-loop bodies once — a ~n_layers× undercount for scanned models.
    The raw XLA numbers are retained as ``xla_reported_*`` for reference.
    """
    from repro.roofline.hlo_cost import analyze_hlo

    cost = analyze_hlo(hlo_text)
    xla = hlo_cost(compiled)
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=cost.flops * chips, hlo_bytes=cost.bytes_tpu * chips,
        attn_score_bytes=cost.attn_score_bytes * chips,
        hlo_bytes_raw=cost.bytes * chips,
        coll_bytes=cost.coll_total * chips,
        coll_breakdown={k: int(v * chips) for k, v in cost.coll.items()},
        model_flops=model_flops(cfg, defs, kind=kind, tokens=tokens),
        per_device_hbm_bytes=per_device_hbm_bytes,
        xla_reported_flops=xla["flops"] * chips,
        xla_reported_bytes=xla["bytes"] * chips,
    )

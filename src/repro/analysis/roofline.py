"""Three-term roofline analysis from compiled XLA artifacts.

    compute term    = HLO_FLOPs / (chips * PEAK_FLOPS)
    memory term     = HLO_bytes / (chips * HBM_BW)
    collective term = collective_bytes / (chips * LINK_BW)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``.  XLA reports
*per-device* numbers for SPMD executables, so the global quantities are
per_device * chips — the chips cancel; we keep the prompt's normalisation
explicit in :func:`roofline_terms`.

collective_bytes is not in cost_analysis: :func:`collective_stats` parses
the post-partitioning HLO (``compiled.as_text()``) and sums the result-shape
bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, per op type.  Result bytes are the standard proxy for
ring traffic (an n-chip ring all-gather moves (n-1)/n of the result bytes
per link — the (n-1)/n ≈ 1 factor is folded into the model's error bars).

Hardware model: TPU v5e-class (197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s
per ICI link/direction).
"""

from __future__ import annotations

import json
import math
import re
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Tuple

from repro.network.fabric import DEFAULT_LINK_BW

PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
LINK_BW = DEFAULT_LINK_BW  # bytes/s per ICI link per direction (repro.network)


def xla_cost_analysis(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a (possibly empty) dict."""
    return dict(compiled.cost_analysis() or {})

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# e.g.  %ag = bf16[16,512,128]{2,1,0} all-gather(%x), ...
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*((?:\([^=]*?\))|(?:[\w\[\],{}:#\s]*?))\s*"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"(?:\.\d+)?\("
)


def _type_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-collective-type {count, bytes} from post-partitioning HLO text."""
    out: Dict[str, Dict[str, float]] = {
        c: {"count": 0, "bytes": 0} for c in _COLLECTIVES
    }
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        type_str, op = m.group(1), m.group(2)
        base = op.replace("-start", "")
        if base not in out:
            continue
        out[base]["count"] += 1
        out[base]["bytes"] += _type_bytes(type_str)
    return out


def total_collective_bytes(stats: Dict[str, Dict[str, float]]) -> float:
    return float(sum(v["bytes"] for v in stats.values()))


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    # per-device quantities from the compiled artifact
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    collectives: Dict[str, Dict[str, float]]
    # model-level accounting
    model_flops: float  # 6*N*D (dense) or 6*N_active*D per step, global
    # memory accounting
    bytes_per_device: Optional[float] = None
    notes: str = ""

    # -- the three terms (seconds) ------------------------------------------------
    @property
    def compute_term(self) -> float:
        return self.hlo_flops * self.chips / (self.chips * PEAK_FLOPS)

    @property
    def memory_term(self) -> float:
        return self.hlo_bytes * self.chips / (self.chips * HBM_BW)

    @property
    def collective_term(self) -> float:
        return self.collective_bytes * self.chips / (self.chips * LINK_BW)

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.compute_term,
            "memory": self.memory_term,
            "collective": self.collective_term,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_lower_bound(self) -> float:
        return max(self.compute_term, self.memory_term, self.collective_term)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / global HLO_FLOPs: how much compiled compute is useful."""
        total = self.hlo_flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Achievable MFU bound: useful FLOPs over the bound-time's compute."""
        t = self.step_time_lower_bound
        if t <= 0:
            return 0.0
        return self.model_flops / (t * self.chips * PEAK_FLOPS)

    def to_json(self) -> Dict[str, Any]:
        d = asdict(self)
        d.update(
            compute_term=self.compute_term,
            memory_term=self.memory_term,
            collective_term=self.collective_term,
            bottleneck=self.bottleneck,
            useful_flops_ratio=self.useful_flops_ratio,
            roofline_fraction=self.roofline_fraction,
        )
        return d


def model_flops_per_step(
    n_params_matmul: float, tokens: float, moe_active_fraction: float = 1.0,
    training: bool = True,
) -> float:
    """6*N*D for training (fwd+bwd), 2*N*D for inference forward."""
    mult = 6.0 if training else 2.0
    return mult * n_params_matmul * moe_active_fraction * tokens


def matmul_param_count(params_shapes) -> float:
    """Parameters participating in matmuls (ndim >= 2 after stacking dims)."""
    import jax

    total = 0
    for leaf in jax.tree.leaves(params_shapes):
        if leaf.ndim >= 2:
            total += leaf.size
    return float(total)

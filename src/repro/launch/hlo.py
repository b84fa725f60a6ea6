"""Compiled-HLO analysis: collective bytes + roofline terms.

``cost_analysis()`` gives HLO FLOPs and bytes but not collective
traffic, so collective bytes are summed from the optimized HLO text:
every ``all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute`` op contributes its *output* shape bytes (the
standard first-order payload estimate; ring all-reduce moves
``2(N-1)/N x`` of that — noted in EXPERIMENTS.md).

Hardware model (TPU v5e, per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional

PEAK_FLOPS = 197e12      # bf16 FLOP/s per chip
HBM_BW = 819e9           # bytes/s per chip
ICI_BW = 50e9            # bytes/s per link



_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[^)=]*\)?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        nbytes = _DTYPE_BYTES.get(dtype)
        if nbytes is None:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * nbytes
    return total


@dataclass
class CollectiveStats:
    bytes_by_op: Dict[str, int] = field(default_factory=dict)
    count_by_op: Dict[str, int] = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_op.values())


def collective_stats(hlo_text: str) -> CollectiveStats:
    """Sum output-shape bytes of every collective in an HLO module.

    ``-start`` variants are counted; their matching ``-done`` (which
    repeats the shape) is skipped to avoid double counting.
    """
    st = CollectiveStats()
    for line in hlo_text.splitlines():
        if "-done(" in line or "-done." in line:
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        st.bytes_by_op[op] = st.bytes_by_op.get(op, 0) + b
        st.count_by_op[op] = st.count_by_op.get(op, 0) + 1
    return st


@dataclass
class Roofline:
    """Three-term roofline for one compiled per-device module."""

    flops: float                 # per-device HLO flops
    hbm_bytes: float             # per-device HLO bytes accessed
    collective_bytes: float      # per-device collective payload bytes
    n_chips: int
    model_flops: Optional[float] = None  # analytic 6*N*D (global)

    @property
    def compute_s(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline-optimistic step time: max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> Optional[float]:
        """MODEL_FLOPS / (per-device HLO flops x chips)."""
        if self.model_flops is None or self.flops == 0:
            return None
        return self.model_flops / (self.flops * self.n_chips)

    @property
    def mfu_bound(self) -> Optional[float]:
        """Model-FLOPs utilization at the roofline step time."""
        if self.model_flops is None:
            return None
        return self.model_flops / (self.n_chips * PEAK_FLOPS * self.step_time_s)

    def as_dict(self) -> Dict:
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "collective_bytes_per_device": self.collective_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "step_time_s": self.step_time_s,
            "model_flops": self.model_flops,
            "useful_flops_fraction": self.useful_flops_fraction,
            "mfu_bound": self.mfu_bound,
            "n_chips": self.n_chips,
        }

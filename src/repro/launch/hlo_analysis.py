"""Roofline-term extraction from compiled XLA artifacts.

``cost_analysis`` supplies HLO FLOPs and bytes; collective traffic is NOT in
cost_analysis, so we parse the (post-SPMD-partitioning) HLO text and sum the
result-shape bytes of every collective op, bucketed by kind.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16 FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link (report vs chips*link_bw)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\([^=]*?\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"[\s(]")


def shape_bytes(type_str: str) -> int:
    """Sum the byte size of every typed shape in an HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    """Collective-op traffic parsed out of HLO text, bucketed by kind."""

    bytes_by_kind: dict = field(default_factory=dict)
    count_by_kind: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        """All collective result bytes across kinds."""
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Scan (post-SPMD) HLO text and sum result bytes per collective kind
    (cost_analysis does not report collective traffic)."""
    stats = CollectiveStats()
    for m in _OP_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        kind = kind.replace("-start", "")
        b = shape_bytes(type_str)
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + b
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
    return stats


@dataclass
class Roofline:
    """Roofline terms for one compiled program on a ``chips``-wide fleet;
    ``t_*`` are per-step lower-bound times against v5e peak rates."""

    flops: float                 # whole-program HLO FLOPs (all chips)
    hbm_bytes: float             # whole-program bytes accessed (all chips)
    collective_bytes: float      # whole-program collective result bytes
    chips: int
    model_flops: float = 0.0     # 6·N·D analytic useful FLOPs

    @property
    def t_compute(self) -> float:
        """Seconds if compute-bound (flops / fleet peak FLOP/s)."""
        return self.flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        """Seconds if HBM-bound (bytes / fleet HBM bandwidth)."""
        return self.hbm_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        """Seconds if interconnect-bound (collective bytes / ICI bw)."""
        return self.collective_bytes / (self.chips * ICI_BW)

    @property
    def dominant(self) -> str:
        """Which roofline term bounds the step: compute/memory/collective."""
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """Analytic model FLOPs over HLO FLOPs (padding/rematerialisation
        overhead shows up as a ratio below 1)."""
        return self.model_flops / self.flops if self.flops else 0.0

    def as_dict(self) -> dict:
        """Flatten to the JSONL record emitted by the dry-run."""
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes, "chips": self.chips,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective, "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_flops_ratio,
        }


def roofline_from_compiled(compiled, chips: int, *,
                           model_flops: float = 0.0) -> Roofline:
    """Build a :class:`Roofline` from a jax ``Compiled`` object."""
    cost = compiled.cost_analysis()
    # XLA reports per-partition numbers for SPMD modules; scale to the fleet.
    flops = float(cost.get("flops", 0.0)) * chips
    byts = float(cost.get("bytes accessed", 0.0)) * chips
    stats = parse_collectives(compiled.as_text())
    return Roofline(flops=flops, hbm_bytes=byts,
                    collective_bytes=float(stats.total_bytes) * chips,
                    chips=chips, model_flops=model_flops)

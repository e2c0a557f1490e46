"""Roofline terms from a compiled dry-run artifact (no real hardware).

Three terms per (arch × shape × mesh), in seconds (DESIGN.md / task spec):

  compute    = HLO_FLOPs_per_device / peak_FLOP/s
  memory     = HLO_bytes_per_device / HBM_bw
  collective = wire_bytes_per_device / link_bw

``compiled.cost_analysis()`` runs on the post-SPMD-partitioning per-device
module, so flops/bytes are already per-chip.  Collective bytes are NOT in
cost_analysis — we parse the optimized HLO text and sum a per-op wire-byte
model over every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (ring-algorithm byte counts, per participating device).

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional

# -- TPU v5e ------------------------------------------------------------------
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link
HBM_PER_CHIP = 16e9          # v5e HBM capacity
VMEM_BYTES = 16 * 2 ** 20    # ~16 MiB VMEM per core — the budget a kernel's
                             # double-buffered tile set must fit
                             # (analysis.pallas_check audits this statically)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?P<shapes>\(?[a-z0-9]+\[[0-9,]*\][^=]*?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<start>-start)?\(")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # iota format [num_groups, group_size]<=[total]
        return int(m.group(2))
    return default


@dataclass
class CollectiveOp:
    op: str
    result_bytes: int
    group_size: int

    @property
    def wire_bytes(self) -> float:
        """Ring-algorithm bytes on the wire per participating device."""
        n, b = self.group_size, self.result_bytes
        if n <= 1:
            return 0.0
        return {
            "all-gather": b * (n - 1) / n,
            "all-reduce": 2 * b * (n - 1) / n,
            "reduce-scatter": b * (n - 1),          # result is 1/n of input
            "all-to-all": b * (n - 1) / n,
            "collective-permute": float(b),
        }[self.op]


def parse_collectives(hlo_text: str, total_devices: int) -> List[CollectiveOp]:
    ops: List[CollectiveOp] = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        ops.append(CollectiveOp(
            op=m.group("op"),
            result_bytes=_shape_bytes(m.group("shapes")),
            group_size=_group_size(line, total_devices),
        ))
    return ops


def collective_summary(ops: List[CollectiveOp]) -> Dict[str, Dict[str, float]]:
    out: Dict[str, Dict[str, float]] = {}
    for op in ops:
        d = out.setdefault(op.op, {"count": 0, "result_bytes": 0, "wire_bytes": 0.0})
        d["count"] += 1
        d["result_bytes"] += op.result_bytes
        d["wire_bytes"] += op.wire_bytes
    return out


@dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    collectives: Dict[str, Dict[str, float]] = field(default_factory=dict)
    model_flops_global: float = 0.0
    num_chips: int = 1
    xla_flops: float = 0.0               # raw cost_analysis (loop bodies ×1)
    xla_bytes: float = 0.0

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.wire_bytes_per_device / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO flops × chips) — remat/redundancy waste."""
        hlo_global = self.flops_per_device * self.num_chips
        return self.model_flops_global / hlo_global if hlo_global else 0.0

    def to_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "wire_bytes_per_device": self.wire_bytes_per_device,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops_global": self.model_flops_global,
            "useful_flops_ratio": self.useful_flops_ratio,
            "num_chips": self.num_chips,
            "collectives": self.collectives,
            "xla_flops": self.xla_flops,
            "xla_bytes": self.xla_bytes,
        }


def analyze(compiled, hlo_text: str, *, num_chips: int,
            model_flops_global: float) -> Roofline:
    """Preferred path: trip-count-aware HLO cost model (hlo_cost.py).

    ``compiled.cost_analysis()`` counts while bodies once (a 52-layer scan
    contributes one layer), so its numbers are kept only as a cross-check
    (``xla_*`` fields in the record).
    """
    from repro.launch import hlo_cost

    cost = hlo_cost.analyze_hlo(hlo_text, total_devices=num_chips)
    xla = dict(compiled.cost_analysis() or {})
    r = Roofline(
        flops_per_device=cost.flops,
        bytes_per_device=cost.bytes_accessed,
        wire_bytes_per_device=cost.wire_bytes,
        collectives=cost.collectives,
        model_flops_global=model_flops_global,
        num_chips=num_chips,
    )
    r.xla_flops = float(xla.get("flops", 0.0))
    r.xla_bytes = float(xla.get("bytes accessed", 0.0))
    return r


def model_flops(cfg, shape, *, active: bool = True) -> float:
    """6·N·D (train) / 2·N·D (prefill) / 2·N·B (one decode tick)."""
    n = cfg.active_param_count() if active else cfg.param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch          # decode: one token per seq


def fmt_seconds(s: float) -> str:
    if s >= 1:
        return f"{s:7.2f}s "
    if s >= 1e-3:
        return f"{s * 1e3:7.2f}ms"
    return f"{s * 1e6:7.2f}us"

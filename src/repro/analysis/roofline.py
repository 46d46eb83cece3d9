"""Roofline terms from a compiled dry-run artifact (no real hardware).

    compute term    = HLO_FLOPs_per_chip / peak_FLOPs
    memory term     = HLO_bytes_per_chip / HBM_bw
    collective term = per-chip link bytes / link_bw

``compiled.cost_analysis()`` on the post-SPMD module reports the *per-device*
program, so terms are per-chip directly (equivalent to the global/chips form
in the spec).  Collective bytes are parsed from the compiled HLO text:
per-chip link traffic ≈ factor · operand_bytes with the standard ring
factors (all-reduce 2×, all-gather/reduce-scatter/all-to-all/permute 1×).

Hardware model: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Optional

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"%?([\w.\-]+)\s*=\s*(\(?[a-z0-9e\[\],{}\s/]*?\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(", re.IGNORECASE)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")

_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
           "all-to-all": 1.0, "collective-permute": 1.0}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Per-chip link-byte estimate + op counts from compiled (per-device) HLO."""
    stats: dict = {k: {"count": 0, "bytes": 0} for k in _FACTOR}
    for line in hlo_text.splitlines():
        line_s = line.strip()
        m = _COLL_RE.search(line_s)
        if not m:
            continue
        kind = m.group(3).lower()
        if m.group(4) == "-done":
            continue  # paired with -start; avoid double counting
        # result shape(s) appear between '=' and the op name
        pre = line_s.split("=", 1)[1].split(kind)[0]
        rbytes = _shape_bytes(pre)
        stats[kind]["count"] += 1
        stats[kind]["bytes"] += int(rbytes * _FACTOR[kind])
    stats["total_bytes"] = sum(v["bytes"] for k, v in stats.items()
                               if isinstance(v, dict))
    stats["total_count"] = sum(v["count"] for k, v in stats.items()
                               if isinstance(v, dict))
    return stats


@dataclasses.dataclass
class Roofline:
    arch: str
    cell: str
    mesh: str
    chips: int
    flops_per_chip: float
    hbm_bytes_per_chip: float
    link_bytes_per_chip: float
    model_flops: int
    model_flops_6nd: int
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    bottleneck: str = ""
    step_s: float = 0.0
    useful_ratio: float = 0.0
    roofline_fraction: float = 0.0
    collectives: Optional[dict] = None
    memory_analysis: Optional[dict] = None

    def finalize(self) -> "Roofline":
        self.compute_s = self.flops_per_chip / PEAK_FLOPS
        self.memory_s = self.hbm_bytes_per_chip / HBM_BW
        self.collective_s = self.link_bytes_per_chip / ICI_BW
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        self.bottleneck = max(terms, key=terms.get)
        # overlap model: perfectly-overlapped roofline step = max of terms
        self.step_s = max(terms.values())
        total_hlo_flops = self.flops_per_chip * self.chips
        self.useful_ratio = (self.model_flops / total_hlo_flops
                             if total_hlo_flops else 0.0)
        ideal_s = self.model_flops / (self.chips * PEAK_FLOPS)
        self.roofline_fraction = ideal_s / self.step_s if self.step_s else 0.0
        return self

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))


def from_compiled(arch: str, cell: str, mesh_name: str, chips: int,
                  compiled, model_fl: dict) -> Roofline:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    coll = collective_stats(hlo) if hlo else {"total_bytes": 0}
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {k: int(getattr(ma, k)) for k in
                   ("argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes")
                   if hasattr(ma, k)}
    except Exception:
        mem = None
    r = Roofline(arch=arch, cell=cell, mesh=mesh_name, chips=chips,
                 flops_per_chip=flops, hbm_bytes_per_chip=hbm,
                 link_bytes_per_chip=float(coll.get("total_bytes", 0)),
                 model_flops=model_fl["model_flops"],
                 model_flops_6nd=model_fl["model_flops_6nd"],
                 collectives=coll, memory_analysis=mem)
    return r.finalize()

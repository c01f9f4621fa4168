"""Roofline accounting from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), all in seconds:

  compute    = HLO_FLOPs_per_device / peak_FLOP/s
  memory     = HLO_bytes_per_device / HBM_bw
  collective = wire_bytes_per_device / ICI_link_bw

Calibrated facts driving the method (measured in this container, JAX 0.8.2 /
XLA CPU backend): ``compiled.cost_analysis()`` reports PER-DEVICE numbers and
counts a ``lax.scan`` body ONCE (not x trip count). Therefore exact totals
come from DELTA LOWERING: each family exposes ``roofline_units(cfg)`` =
(base_cfg, [(count_i, unit_cfg_i)]); lowering base and unit configs gives

  total = cost(base) + sum_i count_i * (cost(unit_i) - cost(base))

The same delta handles collectives inside scan bodies. Collective wire bytes
are parsed from the per-device HLO text (result-shape bytes, replica-group
size aware) with ring-algorithm multipliers:

  all-reduce        2 * R * (n-1)/n      (reduce-scatter + all-gather ring)
  all-gather        R * (n-1)/n          (R = gathered result)
  reduce-scatter    R * (n-1)            (input = n*R)
  all-to-all        R * (n-1)/n
  collective-permute R
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np

from repro.roofline import hw

COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w+\[[\d,]*\](?:\{[^}]*\})?))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
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


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # iota format [G, S] <= [N]: G groups of size S
        return int(m.group(2))
    return default


def wire_multiplier(op: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (n - 1) / n
    if op == "all-gather":
        return (n - 1) / n
    if op == "reduce-scatter":
        return float(n - 1)
    if op == "all-to-all":
        return (n - 1) / n
    return 1.0  # collective-permute


def parse_collectives(hlo_text: str, default_group: int) -> Dict[str, Dict[str, float]]:
    """Per collective type: op count, result bytes, ring wire bytes/device."""
    out: Dict[str, Dict[str, float]] = {
        op: {"count": 0, "result_bytes": 0.0, "wire_bytes": 0.0}
        for op in COLLECTIVE_OPS
    }
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        type_str = m.group(1) or m.group(2)
        op = m.group(3)
        rb = _shape_bytes(type_str)
        n = _group_size(line, default_group)
        rec = out[op]
        rec["count"] += 1
        rec["result_bytes"] += rb
        rec["wire_bytes"] += rb * wire_multiplier(op, n)
    return out


def total_wire_bytes(colls: Dict[str, Dict[str, float]]) -> float:
    return sum(v["wire_bytes"] for v in colls.values())


# --------------------------------------------------------------------------- #
@dataclass
class CostSample:
    """What one lower+compile yields."""

    flops: float = 0.0                 # per device, scan-body-once
    bytes_accessed: float = 0.0        # per device, scan-body-once
    wire_bytes: float = 0.0            # per device, scan-body-once
    collectives: Dict = field(default_factory=dict)
    mem: Dict = field(default_factory=dict)
    compile_seconds: float = 0.0

    @staticmethod
    def from_compiled(compiled, default_group: int, compile_seconds: float = 0.0):
        ca = compiled.cost_analysis() or {}
        colls = parse_collectives(compiled.as_text(), default_group)
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "code_bytes": int(getattr(ma, "generated_code_size_in_bytes", 0)),
            "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
        }
        return CostSample(
            flops=float(ca.get("flops", 0.0)),
            bytes_accessed=float(ca.get("bytes accessed", 0.0)),
            wire_bytes=total_wire_bytes(colls),
            collectives=colls,
            mem=mem,
            compile_seconds=compile_seconds,
        )


def delta_total(base: CostSample, units) -> Dict[str, float]:
    """units: list of (count, CostSample). Returns corrected totals/device."""
    flops = base.flops
    byts = base.bytes_accessed
    wire = base.wire_bytes
    for count, u in units:
        flops += count * (u.flops - base.flops)
        byts += count * (u.bytes_accessed - base.bytes_accessed)
        wire += count * (u.wire_bytes - base.wire_bytes)
    return {"flops": max(flops, 0.0), "bytes": max(byts, 0.0), "wire": max(wire, 0.0)}


def roofline_terms(flops_dev: float, bytes_dev: float, wire_dev: float) -> Dict[str, float]:
    compute = flops_dev / hw.PEAK_FLOPS_BF16
    memory = bytes_dev / hw.HBM_BW
    coll = wire_dev / hw.ICI_BW_PER_LINK
    terms = {"compute_s": compute, "memory_s": memory, "collective_s": coll}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom
    bound = max(compute, memory, coll)
    terms["roofline_fraction"] = compute / bound if bound > 0 else 0.0
    return terms


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6*N*D train, 2*N*D prefill, 2*N*B decode."""
    from repro.models.registry import model_api

    n_active = model_api(cfg).active_param_count(cfg)
    if shape.kind == "train":
        return 6.0 * n_active * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.tokens
    return 2.0 * n_active * shape.global_batch

"""Roofline analysis from compiled dry-run artifacts (deliverable g).

Three terms per (arch × shape × mesh), in seconds:

    compute    = HLO_FLOPs       / (chips × peak bf16 FLOP/s)
    memory     = HLO_bytes       / (chips × peak HBM B/s)
    collective = collective_B    / (chips × ICI B/s per link)

with the peaks of the target chip, looked up by its ``device_kind`` in
``PEAKS``; a chip that is not in the table is an error, never a default.

``cost_analysis()`` on the SPMD-partitioned module reports PER-DEVICE flops
and bytes — but counts while-loop (scan) bodies ONCE, so we use the
trip-count-aware analyzer in ``hlo_cost`` for flops/bytes.  Collective bytes
come from the same pass: operand sizes of all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute, invocation-weighted.

MODEL_FLOPS = 6·N·D for training (N params — active params for MoE; D
tokens), 2·N_active·tokens for forward-only (prefill/decode) cells; the
ratio MODEL/HLO flags remat and padding waste.
"""
from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass
from typing import Any, Dict, Optional


@dataclass(frozen=True)
class Peaks:
    flops: float  # bf16 FLOP/s
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s per ICI link


# Per-chip peaks keyed by jax ``device_kind`` ("TPU v5 lite" is the v5e).
# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s of chip-to-chip interconnect over 4 links
# (50 GB/s each).
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# matches dtype[shape] tokens, e.g. bf16[16,1024]{1,0}
_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s+[^=]*?\b("
    + "|".join(_COLLECTIVES).replace("-", r"\-")
    + r")(?:-start|-done)?\(([^)]*)\)"
)


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes per collective kind from optimized HLO text."""
    out: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        kind, operands = m.group(1), m.group(2)
        total = sum(
            _shape_bytes(sm.group(1), sm.group(2))
            for sm in _SHAPE_RE.finditer(operands)
        )
        out[kind] += total
        counts[kind] += 1
    out_any: Dict[str, Any] = dict(out)
    out_any["total"] = sum(out.values())
    out_any["counts"] = counts
    return out_any


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    # per-device quantities from the compiled module
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    # derived terms (seconds)
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    # accounting
    model_flops_total: float
    hlo_flops_total: float
    useful_ratio: float  # MODEL_FLOPS / HLO_FLOPs(total)
    roofline_fraction: float  # compute_s / max(all terms) — compute-bound=1
    memory_per_device_bytes: Dict[str, float]
    collective_breakdown: Dict[str, Any]
    note: str = ""

    def to_json(self) -> Dict[str, Any]:
        return asdict(self)


def model_flops(
    cfg, shape, kind: str, chips: int
) -> float:
    """6·N·D train, 2·N·D forward-only (N = active params)."""
    counts = cfg.param_counts()
    n_active = counts["active"]
    if kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: ONE new token per sequence
    return 2.0 * n_active * shape.global_batch


def build_report(
    arch: str,
    shape_name: str,
    mesh_name: str,
    chips: int,
    cost: Dict[str, float],
    hlo_text: str,
    mem: Dict[str, float],
    cfg,
    shape,
    kind: str,
    *,
    device_kind: str,
    note: str = "",
) -> RooflineReport:
    from . import hlo_cost

    peak = peaks_for(device_kind)

    hc = hlo_cost.analyze(hlo_text)
    flops_dev = float(hc.flops)  # trip-count-aware, per device (post-SPMD)
    bytes_dev = float(hc.bytes)
    coll: Dict[str, Any] = dict(hc.collective_detail)
    coll["total"] = hc.collective_bytes
    coll["counts"] = hc.collective_counts
    coll["xla_cost_analysis_flops_scan_once"] = float(cost.get("flops", 0.0))
    coll_dev = float(hc.collective_bytes)

    compute_s = flops_dev / peak.flops
    memory_s = bytes_dev / peak.hbm_bw
    collective_s = coll_dev / peak.ici_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape, kind, chips)
    hlo_total = flops_dev * chips
    bound = max(terms.values())
    return RooflineReport(
        arch=arch,
        shape=shape_name,
        mesh=mesh_name,
        chips=chips,
        flops_per_device=flops_dev,
        bytes_per_device=bytes_dev,
        collective_bytes_per_device=coll_dev,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops_total=mf,
        hlo_flops_total=hlo_total,
        useful_ratio=mf / hlo_total if hlo_total else 0.0,
        roofline_fraction=compute_s / bound if bound > 0 else 0.0,
        memory_per_device_bytes=mem,
        collective_breakdown=coll,
        note=note,
    )

"""Roofline analysis from compiled dry-run artifacts (EXPERIMENTS.md §Roofline).

Three terms, all in seconds-per-step on the target hardware (TPU v5e):

  compute    = HLO_FLOPs_per_device   / (peak bf16 FLOP/s per chip)
  memory     = HLO_bytes_per_device   / (HBM bandwidth per chip)
  collective = collective_bytes_per_device / (ICI link bandwidth)

cost_analysis() supplies FLOPs/bytes of the partitioned per-device program;
collective bytes are NOT in cost_analysis, so we parse the partitioned HLO
and sum result-shape sizes of every collective op.
"""

from __future__ import annotations

import re
from typing import Dict

from repro.configs.base import ModelConfig, ShapeConfig

# TPU v5e hardware constants (per chip)
PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
ICI_BW = 50e9  # per link per direction

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([\d,]*)\][^)]*?\)?\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)

# tuple-result collectives: "= (f32[..], f32[..]) all-to-all(...)"
_COLL_TUPLE_RE = re.compile(
    r"=\s+\(([^)]*)\)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> float:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes_from_hlo(hlo_text: str) -> Dict:
    """Sum result-shape bytes of collective ops in partitioned HLO text."""
    by_kind: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    seen_done = set()
    for line in hlo_text.splitlines():
        if "-done" in line:
            # async pairs: count the start only
            continue
        m = _COLL_TUPLE_RE.search(line)
        if m:
            shapes, kind = m.group(1), m.group(2)
            total = sum(
                _shape_bytes(dt, dims) for dt, dims in _SHAPE_RE.findall(shapes)
            )
        else:
            m = _COLL_RE.search(line)
            if not m:
                continue
            dt, dims, kind = m.group(1), m.group(2), m.group(3)
            total = _shape_bytes(dt, dims)
        by_kind[kind] = by_kind.get(kind, 0.0) + total
        counts[kind] = counts.get(kind, 0) + 1
    return {
        "total": sum(by_kind.values()),
        "by_kind": by_kind,
        "counts": counts,
    }


def roofline_terms(cell: Dict, cfg: ModelConfig, shape: ShapeConfig) -> Dict:
    """Derive the three terms + MODEL_FLOPS ratio for one dry-run cell."""
    t_compute = cell["flops_per_device"] / PEAK_FLOPS_BF16
    t_memory = cell["bytes_per_device"] / HBM_BW
    t_coll = cell["collective_bytes_per_device"] / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)

    # MODEL_FLOPS: 6·N·D for train, 2·N·D forward (per processed token)
    n_active = cell.get("active_params") or cell.get("params") or 0
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:  # decode: one token per sequence
        tokens = shape.global_batch
        model_flops = 2.0 * n_active * tokens
    hlo_total = cell["flops_per_device"] * cell.get("n_devices", 1)
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops": model_flops,
        "useful_flops_ratio": (model_flops / hlo_total) if hlo_total else 0.0,
        "step_time_lb_s": max(terms.values()),
    }

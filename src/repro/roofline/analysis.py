"""Roofline analysis from the compiled dry-run artifact (§Roofline).

Three terms per (arch × shape × mesh), in seconds:

  compute    = HLO_FLOPs_per_device   / peak_FLOP/s_per_chip
  memory     = HLO_bytes_per_device   / HBM_bw_per_chip
  collective = collective_bytes_per_device / ICI_link_bw

Sources: ``compiled.cost_analysis()`` (flops, bytes accessed) and the
post-SPMD HLO text (collective operand/result sizes — cost_analysis does not
cover comm).  All sizes in the partitioned module are per-device.

Hardware constants come from ``PEAKS``, keyed by the ``device_kind`` JAX
reports; a kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import json
import re
from typing import Dict, Optional, Tuple

# Per-chip peaks by ``jax.devices()[i].device_kind``.  Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
# ICI per chip; ~50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,     # bf16 FLOP/s per chip
        "hbm_bw": 819e9,          # B/s per chip
        "ici_bw": 50e9,           # B/s per link
    },
}

# The chip the dry-run's described production meshes stand for.
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks recorded for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 0.5, "u4": 0.5, "pred": 1, "c64": 8, "c128": 16,
}

# matches e.g. ``bf16[16,4096]`` / ``f32[]``
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> float:
    if dtype not in _DTYPE_BYTES:
        return 0.0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, float]:
    """Per-device bytes moved by each collective kind.

    Accounting (ring-algorithm equivalents, per device):
      all-reduce      2 × operand bytes (reduce-scatter + all-gather)
      all-gather      result bytes
      reduce-scatter  operand bytes
      all-to-all      operand bytes
      collective-permute  operand bytes
    Async ``*-start`` forms are counted once; ``*-done`` ignored.
    """
    out = {k: 0.0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        ls = line.lstrip()
        if "fusion" in ls[:60]:
            continue
        m = re.search(
            r"=\s+(.+?)\s+(all-reduce|all-gather|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\(", ls)
        if not m:
            continue
        if re.search(r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)-done", ls):
            continue
        result_part, kind = m.group(1), m.group(2)
        # operand shapes: inside the call parens
        call = ls[m.end():]
        operand_bytes = sum(_shape_bytes(d, s)
                            for d, s in _SHAPE_RE.findall(call))
        result_bytes = sum(_shape_bytes(d, s)
                           for d, s in _SHAPE_RE.findall(result_part))
        if kind == "all-reduce":
            b = 2.0 * operand_bytes
        elif kind == "all-gather":
            b = result_bytes
        else:
            b = operand_bytes
        out[kind] += b
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   coll_bytes_per_dev: float,
                   device_kind: str) -> Dict[str, float]:
    hw = peaks(device_kind)
    t_c = flops_per_dev / hw["peak_flops"]
    t_m = bytes_per_dev / hw["hbm_bw"]
    t_x = coll_bytes_per_dev / hw["ici_bw"]
    terms = {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x}
    dom = max(terms, key=terms.get)
    terms["bottleneck"] = dom.replace("_s", "")
    bound = max(t_c, t_m, t_x)
    terms["roofline_fraction_of_bound"] = (
        t_c / bound if bound > 0 else 0.0)   # compute share of the bound
    return terms


def memory_analysis_dict(compiled) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: float(getattr(ma, k)) for k in keys if hasattr(ma, k)}


def analyze_compiled(compiled, *, chips: int, model_flops: float,
                     shape_kind: str, device_kind: str) -> Dict:
    """Full §Roofline record for one compiled cell.

    Primary flops/bytes/collective figures come from the loop-aware static
    HLO analysis (hlo_cost.py) — XLA's cost_analysis counts while-loop
    bodies once, silently dropping the scanned layer stack.  The raw
    cost_analysis numbers are recorded alongside for reference.
    """
    from repro.roofline.hlo_cost import hlo_static_cost

    cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    static = hlo_static_cost(text)
    flops = float(static["flops"])
    byts = float(static["bytes"])
    coll_total = float(static["collective_total"])
    terms = roofline_terms(flops, byts, coll_total, device_kind)
    mem = memory_analysis_dict(compiled)
    useful = model_flops / (flops * chips) if flops else 0.0
    return {
        "chips": chips,
        "device_kind": device_kind,
        "hlo_flops_per_dev": flops,
        "hlo_bytes_per_dev": byts,
        "collective_bytes_per_dev": coll_total,
        "collective_breakdown": static["collectives"],
        "collective_op_count": static["collective_ops"],
        "unknown_trip_loops": static["unknown_loops"],
        "xla_cost_analysis_flops": float(cost.get("flops", 0.0)),
        "xla_cost_analysis_bytes": float(cost.get("bytes accessed", 0.0)),
        "model_flops_global": model_flops,
        "useful_flops_ratio": useful,
        **terms,
        "memory_analysis": mem,
    }


def model_flops_for(cfg, shape_kind: str, tokens: int) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference); N = active params."""
    n_active = cfg.param_count(active_only=True)
    factor = 6.0 if shape_kind == "train" else 2.0
    return factor * n_active * tokens

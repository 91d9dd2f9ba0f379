"""Roofline-term derivation from compiled dry-run artifacts.

  compute    = HLO_FLOPs   / (chips * PEAK_FLOPS_BF16)
  memory     = HLO_bytes   / (chips * HBM_BW)
  collective = coll_bytes  / (chips * ICI_BW)

FLOPs/bytes come from ``compiled.cost_analysis()``; collective bytes are NOT
reported there, so we parse the optimized HLO text and sum operand sizes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

from repro.core.config import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")

# matches e.g. "bf16[128,4096]{1,0}" (layout suffix optional)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(m: re.Match) -> int:
    dt, dims = m.group(1), m.group(2)
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes per collective kind from (S)HLO text."""
    out = {k: 0 for k in _COLL_OPS}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        eq = s.find("= ")
        if eq < 0:
            continue
        rhs = s[eq + 2:]
        kind = None
        for op in _COLL_OPS:
            # op name appears as "<shape> <op>(" or "<op>-start("
            if f" {op}(" in rhs or f" {op}-start(" in rhs:
                kind = op
                break
        if kind is None:
            continue
        # operand list between the first '(' and matching ')'
        lp = rhs.find("(")
        rp = rhs.rfind(")")
        operands = rhs[lp + 1:rp]
        nbytes = sum(_shape_bytes(m) for m in _SHAPE_RE.finditer(operands))
        out[kind] += nbytes
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLL_OPS)
    return out


def roofline_terms(flops: float, bytes_accessed: float, coll_bytes: float,
                   chips: int) -> Dict[str, float]:
    """All inputs are PER-PARTITION quantities: XLA's cost_analysis() on an
    SPMD-partitioned module reports the per-device module, and the parsed
    HLO shapes are per-device shards.  Per-chip terms therefore divide by
    one chip's peak; global = per-chip x chips when balanced (equivalent to
    the global/(chips*peak) formulation)."""
    compute = flops / PEAK_FLOPS_BF16
    memory = bytes_accessed / HBM_BW
    collective = coll_bytes / ICI_BW
    terms = {"compute_s": compute, "memory_s": memory,
             "collective_s": collective}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom
    terms["bound_s"] = terms[dom]
    return terms


def hlo_cost_analysis(compiled) -> Dict:
    """``compiled.cost_analysis()`` as a dict (an empty/None result becomes
    ``{}``; exceptions propagate to the caller)."""
    return dict(compiled.cost_analysis() or {})


def analyze_compiled(lowered, compiled, chips: int,
                     model_flops: Optional[float] = None) -> Dict:
    cost = hlo_cost_analysis(compiled)
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    coll = collective_bytes(hlo)
    terms = roofline_terms(flops, nbytes, coll["total"], chips)
    mem = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                mem[k] = int(getattr(ma, k, 0))
    except Exception:
        pass
    result = {
        "flops": flops,
        "bytes_accessed": nbytes,
        "collectives": coll,
        "terms": terms,
        "memory": mem,
    }
    if model_flops:
        result["model_flops"] = model_flops
        hlo_global = flops * chips
        result["useful_fraction"] = model_flops / hlo_global \
            if hlo_global else 0.0
    return result

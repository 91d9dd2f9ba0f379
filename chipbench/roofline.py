"""Peaks of the chip and the work each paged attention grid needs.

The counts are of the work the algorithm needs, whatever implements it:

- bytes: every base KV page that the step's block tables reach, each
  counted once however many requests share it; each request's own
  residual pages at their rank-r size; the queries and outputs; and each
  request's LoRA up-projections ``B_k``, ``B_v``;
- FLOPs: ``q k^T`` and ``p v`` over each query's own causal context, and
  the rank-r reconstruction ``K += r_k B_k``, ``V += r_v B_v`` of every
  context token of each request.

A grid that reads shared pages once per group can therefore not read above
100% of its roofline.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Sequence

from chipbench.weights import Dims

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


@dataclasses.dataclass(frozen=True)
class Row:
    """One live request of a grid call: ``q_len`` query tokens at
    positions ``start .. start + q_len - 1``, reading its context through
    ``base_pages`` (the block table's live entries)."""
    start: int
    q_len: int
    base_pages: tuple

    @property
    def extent(self) -> int:
        return self.start + self.q_len


def live_pages(extent: int, page: int) -> int:
    return -(-extent // page)


def grid_work(rows: Sequence[Row], dims: Dims, page: int) -> Dict[str, float]:
    """FLOPs and bytes one layer's call of a paged grid needs."""
    it = dims.itemsize
    hq, hd, r, kvd = dims.heads, dims.head_dim, dims.rank, dims.kv_dim
    unique = set()
    res_pages = q_tok = 0
    attn_ctx = recon_ctx = 0
    for row in rows:
        n = live_pages(row.extent, page)
        unique.update(row.base_pages[:n])
        res_pages += n
        q_tok += row.q_len
        # query i attends to start + i + 1 positions
        attn_ctx += row.q_len * row.start + row.q_len * (row.q_len + 1) // 2
        recon_ctx += row.extent
    base_bytes = len(unique) * 2 * dims.kv_heads * page * hd * it
    res_bytes = res_pages * 2 * page * r * it
    qo_bytes = 2 * q_tok * hq * hd * it
    adapter_bytes = len(rows) * 2 * r * kvd * it
    flops = 4 * hq * hd * attn_ctx + 4 * r * kvd * recon_ctx
    return {"flops": float(flops),
            "bytes": float(base_bytes + res_bytes + qo_bytes + adapter_bytes)}


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: Dict[str, float]) -> float:
    """Least time the chip could take for the work, over the time taken,
    in percent."""
    least = max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def step_flops(rows: Sequence[Row], dims: Dims) -> float:
    """Model FLOPs of the useful (unpadded) tokens of one step: every
    projection with its LoRA offset, attention over each token's own causal
    context, and one row of logits per request."""
    d, r = dims.d_model, dims.rank
    proj = d * (2 * dims.q_dim + 2 * dims.kv_dim) + 3 * d * dims.d_ff
    lora = 3 * d * r + r * (dims.q_dim + 2 * dims.kv_dim)
    total = 0
    for row in rows:
        ctx = row.q_len * row.start + row.q_len * (row.q_len + 1) // 2
        total += dims.layers * (2 * (proj + lora) * row.q_len +
                                4 * dims.heads * dims.head_dim * ctx)
        total += 2 * d * dims.vocab
    return float(total)

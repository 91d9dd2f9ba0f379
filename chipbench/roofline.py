"""Peaks of the chip, and the rows of a step that the architectures'
work counts (``grid_work``, ``step_flops`` in ``chipbench/arch/``) read."""
from __future__ import annotations

import dataclasses
from typing import Dict

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


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: Dict[str, float]) -> float:
    """Least time the chip could take for the work, over the time taken,
    in percent."""
    least = max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds

"""Published peaks of the chips this stack runs on, keyed by
``device.device_kind``, and the batch rows at which a step stops being
bound by its weight reads.

Source: Google Cloud documentation, "TPU v5e" (per chip).
"""

from __future__ import annotations

from typing import Optional

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def ridge_rows(device_kind: str, bytes_per_weight: float = 2.0
               ) -> Optional[int]:
    """The most rows a step can carry while every product with a weight
    matrix stays bound by reading that matrix: a row does 2 operations per
    weight, so the products turn compute-bound past peak FLOP/s / HBM B/s
    x (bytes per weight / 2) rows (240 for bf16 on a v5e). None for a chip
    not in ``PEAKS``."""
    peak = PEAKS.get(device_kind)
    if peak is None:
        return None
    return int(peak["bf16_flops_per_s"] / peak["hbm_bytes_per_s"]
               * bytes_per_weight / 2)

"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. A device that is not in the table is an error: a share of
a peak is never computed against a default."""

from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s
# bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
    "TPU v5e": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9,
                "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; raises for a kind not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in the benchmark's table "
            f"of peaks ({sorted(PEAKS)}); add it with its published "
            f"source before reporting a share of a peak") from None

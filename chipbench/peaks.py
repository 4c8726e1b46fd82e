"""Published peaks of each chip the benchmark may run on, keyed by JAX's
``device_kind``.  A device that is not here is an error, not a default."""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM2 at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> Dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

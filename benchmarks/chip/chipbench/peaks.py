"""Published peaks of each chip the benchmark may run on, keyed by JAX's
``device_kind``. A device that is not here is an error, not a default.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture): 197
TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s. The float32 engine's
matmuls run at JAX's default precision, one bf16 pass on the MXU, so the
bf16 peak is the denominator of every share of peak compute here.
"""
from __future__ import annotations

_V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9, "source": "Google Cloud documentation, TPU v5e"}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"chipbench: no published peaks for device kind "
                         f"{device_kind!r}; add it to peaks.py") from None

"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.  A kind
that is not here is an error, never a default."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 394 TOP/s
    # int8, 16 GB of HBM at 819 GB/s
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, 'TPU v5e'"},
}


def chip_peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in chipbench/peaks.py") from None

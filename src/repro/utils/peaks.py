"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The one table every roofline reads (``benchmarks/roofline.py`` over the
kernel records, ``launch/dryrun.py`` / ``launch/fedsim.py`` for their
compile-only estimates).  A device kind that is not in the table is an
error, never a default: a roofline share against the wrong chip's peaks
is a wrong number.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 394 TOP/s
    # int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
    # interconnect per chip (four links of 400 Gbit/s in a 2D torus)
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                    "ici_link_bytes_s": 50e9,
                    "source": "Google Cloud documentation, 'TPU v5e'"},
}


def chip_peaks(device_kind: str) -> dict:
    """Peaks of one chip of this ``device_kind``; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to repro/utils/peaks.py "
                       f"with their source") from None

"""Peak rates of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip. The
served kernels multiply MX values, which bf16 holds exactly, so the bf16
rate is the compute peak. A kind not in the table is an error.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"add its published numbers to chipbench/peaks.py"
                       ) from None

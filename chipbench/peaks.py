"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip, 16 GB of HBM.
There is no f32 peak in that table. Dividing f32 work by the bf16 peak
makes the compute bound optimistic, so a roofline share can only read low.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to chipbench/peaks.py with its source") from None

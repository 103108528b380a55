"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): per chip 197 TFLOP/s in bfloat16, 393 TOP/s in int8, 16 GB of HBM
at 819 GB/s.  A float32 matmul at JAX's default precision runs on the
TPU's matrix units as single bfloat16 passes, so the bfloat16 peak is the
denominator of every roofline and ``mfu`` share.  A device that is not in
the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

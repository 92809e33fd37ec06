"""Published peaks of each chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip. The models run
float32 matmuls at the default precision, which XLA does as one bf16
pass on the MXU, so the bf16 peak is the one their FLOPs are held to.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None

"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 394 TOP/s int8, 819 GB/s HBM bandwidth, 16 GiB HBM per
chip.  The program's float32 matmuls run at XLA's DEFAULT precision, which
on a TPU is one bf16 pass, so the bf16 peak is the one a float32 step is
held against.  A device kind that is not listed is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 394e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 1024 ** 3},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports
it. A device that is not here is an error, never a default."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2e at 819 GB/s per chip.
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device {device_kind!r}; "
                       f"add it to benchmark/harness/peaks.py with its source")
    return PEAKS[device_kind]


def least_seconds(flops: float, bytes_moved: float, device_kind: str):
    """The least time the chip could take and which bound sets it."""
    p = peaks(device_kind)
    t_c, t_m = flops / p["bf16_flops_per_s"], bytes_moved / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")

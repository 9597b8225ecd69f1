"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` JAX reports. A kind that is not in the table is an error,
never a default: a share of a peak the table does not hold is a guess."""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e" system architecture page:
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a row "
            f"with its source to benchmark/lib/peaks.py") from None

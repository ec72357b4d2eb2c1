"""Published peaks per chip, keyed by the exact ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture — per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s. A kind that
is not in this table is an error, never a default. (Copied from
``bench.py`` ``PEAK_BF16_TFLOPS``; HBM and int8 added.)
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise ValueError(
            f"no published {what} on record for device_kind "
            f"{device_kind!r}; add it to benchmarks/harness/peaks.py with "
            f"its source") from None

"""The yardstick's table of peaks, keyed by `device_kind` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.  A
device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2 ** 30,
        "source": "Google Cloud documentation, TPU v5e",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def lookup(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}: add it to "
            f"benchmarks/harness/peaks.py with its source "
            f"(known: {sorted(PEAKS)})") from None

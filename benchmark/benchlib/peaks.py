"""The one table of chip peaks the benchmark divides by, keyed by the
``device_kind`` JAX reports. No default row: a kind that is not listed is an
error, never somebody else's chip."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB HBM at 819 GB/s. JAX reports the chip as "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(f"benchmark: no peak row for device kind {device_kind!r}; "
                         f"have {sorted(PEAKS)}. Add a row with its source.")
    return PEAKS[device_kind]

"""Published peaks of the chips the benchmark has run on, keyed by the
``device_kind`` JAX reports. A device that is not here is an error, not a
default: a utilization against a guessed peak is not a measurement."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,      # bf16 matrix unit
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"with its source to cellbench/peaks.py (known: {sorted(PEAKS)})"
        ) from None

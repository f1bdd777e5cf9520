"""Published peaks of one chip, keyed by ``device_kind`` as jax reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip. A kind that is not in
the table is an error, never a default (``bench.py::_peak_lookup`` holds
the program's own copy of the same numbers).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add a "
            "row to benchmark/peaks.py with its source"
        )
    return PEAKS[device_kind]

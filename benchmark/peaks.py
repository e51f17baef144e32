"""Peaks of the chips the benchmark knows, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture table):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip,
1,600 Gbit/s chip-to-chip interconnect.  ``jax.devices()[0].device_kind``
reports that chip as "TPU v5 lite" (libtpu 0.0.34).  A kind that is not in
the table is an error: a share of an unknown peak is not a number.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add it to "
            "benchmark/peaks.py with its source") from None

"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A device that is not in the table is an error: a share
of a peak is never computed against a guess."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def lookup(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add its "
                       "published numbers to benchmarks/chip/chipbench/peaks.py")
    return PEAKS[device_kind]

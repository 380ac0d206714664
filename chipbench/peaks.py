"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  The one table of them in the repository.

Source: Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s in
bfloat16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect.

The entity-matching kernels take float32 operands, for which the chip
has no published peak; their roofline is taken against the bfloat16
peak, which overstates what the chip can do in float32, so a kernel's
roofline share is a lower bound.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,  # bfloat16
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks(device_kind: str) -> dict:
    """Peaks of ``device_kind``; a chip not in the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]

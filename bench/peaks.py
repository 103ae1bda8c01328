"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it, and
the roofline against them.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect.  A device that is not in the table is an error, never a
default: a roofline against the wrong chip's peaks is a wrong number.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; raises ``KeyError`` for a chip
    that has no entry."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} (known: {sorted(PEAKS)})"
        ) from None


def least_time(work: dict, peak: dict) -> tuple[float, str]:
    """Roofline: the larger of FLOPs over peak FLOP/s and bytes over HBM
    bandwidth, and which one bounds."""
    t_flops = work["flops"] / peak["bf16_flops"]
    t_bytes = work.get("bytes", 0.0) / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")

"""Peaks of the chip, and the least time a piece of work could take on it.

The work itself (operations and bytes a step needs, counted from shapes) is
the architecture module's ``shapes(c)`` (``bench.arch``).
"""
from __future__ import annotations

#: Published peaks of one chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture page):
#: 197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"flops": 197e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
}
SOURCE = 'Google Cloud documentation, "TPU v5e"'


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table entry for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def roofline_seconds(flops: float, nbytes: float, peak: dict):
    """Least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["flops"]
    t_bytes = nbytes / peak["hbm_bytes_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")

"""Published peaks of the chips the benchmark runs on, keyed by `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip.  A device
kind missing from the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,  # bf16 matrix units
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peak(device_kind: str) -> dict:
    """The peak table row of one device kind; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str) -> float:
    """Least time the chip could take for the work, over the time it took, in %.

    The least time is the larger of flops over peak FLOP/s and bytes over
    peak HBM bandwidth."""
    p = peak(device_kind)
    least = max(flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / seconds

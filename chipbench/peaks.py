"""Published peaks per chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s).  A kind that is not in the table is
an error, never a default: a roofline against the wrong chip's peak is a
wrong number.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; have "
            f"{sorted(PEAKS)}"
        ) from None


def roofline_pct(bytes_moved: int, peak: dict | None, device_s: float | None):
    """The least time of a memory-bound call (its bytes at the HBM peak) as
    a percentage of the time it took on the device; ``None`` without a
    device time or a peak."""
    if peak is None or not device_s:
        return None
    return 100.0 * bytes_moved / peak["hbm_bytes_per_s"] / device_s

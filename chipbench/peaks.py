"""Published peaks of each device kind the benchmark may run on.

Keyed by ``jax.Device.device_kind``. A kind that is not in the table is an
error, never a default: a roofline share against a guessed peak is no
measurement.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    hbm_bytes_per_s: float
    bf16_flops_per_s: float
    int8_ops_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12, int8_ops_per_s=393e12,
        hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s per chip"),
}


class UnknownDevice(KeyError):
    """The device kind has no entry in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peaks: Peaks) -> float:
    """Least time over measured time, in percent. The least time is the
    larger of ``ops`` at the bf16 peak and ``nbytes`` at the HBM peak."""
    if seconds <= 0:
        raise ValueError("a roofline share needs a positive device time")
    least = max(ops / peaks.bf16_flops_per_s, nbytes / peaks.hbm_bytes_per_s)
    return 100.0 * least / seconds

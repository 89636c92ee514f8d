"""Published per-chip peaks, keyed by the exact ``device_kind`` string
JAX reports — the one table every utilisation figure divides by.

A device that is not in the table is an error on platform ``tpu``, not
a default: a utilisation against a guessed peak is noise.  Off the chip
there is no peak to divide by and callers get ``None``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_flops_per_sec: float
    hbm_bytes_per_sec: float
    source: str


PEAKS = {
    # jax.devices()[0].device_kind on the v5e (chip_smoke run, PR 21)
    "TPU v5 lite": DevicePeaks(
        bf16_flops_per_sec=197e12,
        hbm_bytes_per_sec=819e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def device_peaks(device: Optional[jax.Device] = None) -> Optional[DevicePeaks]:
    """Peaks of ``device`` (default: the first device); ``None`` off-TPU."""
    device = device if device is not None else jax.devices()[0]
    if device.platform != "tpu":
        return None
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise RuntimeError(
            f"no peaks recorded for device_kind {device.device_kind!r}; "
            f"add its published figures to utils/device_peaks.PEAKS"
        ) from None


__all__ = ["DevicePeaks", "PEAKS", "device_peaks"]

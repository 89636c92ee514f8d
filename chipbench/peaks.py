"""Published per-chip peaks, keyed by the exact ``device_kind`` JAX reports.

The benchmark's own copy of ``utils/device_peaks.py`` (the yardstick may
not move with the program).  A TPU that is not in the table is an error,
never a default.
"""
from __future__ import annotations

PEAKS = {
    # jax.devices()[0].device_kind on the v5e (chip run, PR 21)
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no peaks recorded for device_kind {device_kind!r}; add its "
            f"published figures to chipbench/peaks.py"
        ) from None

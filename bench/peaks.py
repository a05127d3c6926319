"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error."""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float | str]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture",
    },
}


def peaks_for(device_kind: str) -> Dict[str, float | str]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to bench/peaks.py with their source") from None


def min_seconds(flops: float, nbytes: float, peaks: Dict[str, float | str]
                ) -> float:
    """The least time the chip needs for the work: the larger of its
    operations over peak FLOP/s and its bytes over peak bandwidth."""
    return max(flops / float(peaks["bf16_flops_per_s"]),
               nbytes / float(peaks["hbm_bytes_per_s"]))

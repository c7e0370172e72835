"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` JAX reports. A chip that is not here is refused: a
roofline or utilization against a guessed peak means nothing."""
from __future__ import annotations

from typing import Dict

SOURCE = "Google Cloud documentation, 'TPU v5e' (per chip)"

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


class UnknownChip(ValueError):
    pass


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownChip(
            f"device_kind {device_kind!r} is not in the peaks table "
            f"(known: {sorted(PEAKS)})") from None

"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` that JAX reports. A device that is not listed is an error:
a share of a peak is never computed against a guessed one."""

from __future__ import annotations

import dataclasses

__all__ = ["Peaks", "PEAKS", "peaks_for"]


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # FLOP/s per chip, dense bf16 on the matrix units
    hbm_bytes_s: float  # HBM bytes/s per chip
    hbm_bytes: int  # HBM capacity per chip
    ici_bits_s: float  # chip-to-chip interconnect bits/s per chip
    source: str


_V5E = Peaks(
    bf16_flops=197e12,
    hbm_bytes_s=819e9,
    hbm_bytes=16 * 10**9,
    ici_bits_s=1600e9,
    source="Google Cloud documentation, 'TPU v5e' system architecture table",
)

PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None

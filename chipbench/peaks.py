"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

A device that is not in the table is an error, never a default: a roofline
share against the wrong peak is a wrong number.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_per_s: float   # dense bf16 matrix units
    bytes_per_s: float   # HBM bandwidth
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        flops_per_s=197e12,
        bytes_per_s=819e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, TPU v5e",
    ),
}


class UnknownDevice(KeyError):
    pass


def peak_for(device_kind: str) -> Peak:
    """The published peaks of ``device_kind``; raises :class:`UnknownDevice`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None

"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device missing from the table is an error, never a default: a share of
a peak that silently used another chip's peak would mean nothing.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # FLOP/s, dense bf16 matmul on the MXU
    hbm_bytes_per_s: float
    hbm_bytes: float
    ici_bits_per_s: float
    source: str


TABLE = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        ici_bits_per_s=1600e9,
        source="Google Cloud documentation, 'TPU v5e' (per chip)",
    ),
}


def for_kind(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}; known: {sorted(TABLE)}"
        ) from None

"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A kind that is not in the table is an error: a roofline share or an MFU
against a guessed peak is no measurement.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    bf16_flops: float     # FLOP/s, dense bf16 matrix units
    hbm_bytes_s: float    # bytes/s of device memory
    hbm_bytes: float      # bytes of device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        bf16_flops=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s per chip.
JAX names that chip "TPU v5 lite".

The programs measured here keep float32 parameters; the MXU's peak is
quoted for bf16, and a float32 product is one bf16 pass at JAX's default
precision and six at ``highest``.  The bf16 peak is therefore the ceiling a
model FLOP utilisation is measured against, whatever the precision.  A device that is not in the table is an error, never a
default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float     # FLOP/s
    hbm_bytes_s: float    # bytes/s
    hbm_bytes: float      # bytes of device memory
    source: str


_V5E = Peaks(bf16_flops=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
             source="Google Cloud documentation, TPU v5e")

TABLE = {"TPU v5 lite": _V5E}


class UnknownDeviceError(KeyError):
    pass


def for_kind(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device_kind {device_kind!r}; the table "
            f"knows {sorted(TABLE)}") from None

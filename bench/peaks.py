"""Published peaks per chip, keyed by `jax.Device.device_kind`.

TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM (Google Cloud
documentation, "TPU v5e").  JAX names the chip "TPU v5 lite".  A kind that
is not in the table is an error: a share of an unknown peak is no number.
"""
from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    bf16_flops: float  # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: int


_V5E = Peak(197e12, 819e9, 16 * 10**9)

PEAKS: dict[str, Peak] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

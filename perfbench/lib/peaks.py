"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s interconnect).
The chip publishes no float32 or float64 peak; FedNL runs emulated float64,
so a share of the bf16 peak is a share of the only published FLOP peak and
reads far below 1%.

A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    name: str
    flops: float  # FLOP/s, bf16
    hbm_bytes_per_s: float
    hbm_bytes: float


TPU_V5E = Peaks("TPU v5e", 197e12, 819e9, 16e9)

PEAKS = {"TPU v5 lite": TPU_V5E, "TPU v5e": TPU_V5E}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None

"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default: a share of a guessed peak is not a measurement.

Source: Google Cloud documentation, "TPU v5e" system architecture page —
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect per chip.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    ici_bits_per_s: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        ici_bits_per_s=1600e9,
        source='Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in the benchmark's peaks "
            f"table ({sorted(PEAKS)}); add it with its source, do not "
            f"guess") from None

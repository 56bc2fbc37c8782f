"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A chip that is not in the table is an
error, never a default."""
from __future__ import annotations

from dataclasses import dataclass

SOURCE = ("Google Cloud documentation, 'TPU v5e' "
          "(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, "
          "819 GB/s HBM, 16 GB HBM per chip")


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s per chip
    hbm_bytes_per_s: float   # bytes/s per chip
    hbm_bytes: float         # bytes per chip
    source: str


V5E = Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
            source=SOURCE)

# JAX reports a v5e chip as "TPU v5 lite"
PEAKS = {"TPU v5 lite": V5E, "TPU v5e": V5E}


class UnknownDevice(ValueError):
    """The attached chip has no entry in the peaks table."""


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})") from None

"""TPU hardware model (v5e-class target; the container is CPU-only, so these
constants drive the cost model and the roofline denominators)."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class HardwareSpec:
    name: str = "tpu-v5e"
    peak_flops_bf16: float = 197e12      # per chip
    hbm_bw: float = 819e9                # bytes/s per chip
    hbm_bytes: float = 16 * 2**30        # capacity per chip
    ici_bw: float = 50e9                 # bytes/s per link (spec-given)
    ici_links: int = 2                   # usable links per chip (conservative)
    dci_bw: float = 6.25e9               # inter-pod (pod axis) per chip
    host_bw: float = 25e9                # host<->HBM per chip (offload path)
    mxu_min_dim: int = 128               # MXU tile alignment
    vmem_bytes: float = 16 * 2**20       # on-core vector memory (per core)

    @property
    def ici_bw_total(self) -> float:
        return self.ici_bw * self.ici_links


V5E = HardwareSpec()

# the ``device_kind`` strings JAX reports for the chips specified above
_BY_DEVICE_KIND = {"TPU v5 lite": V5E, "TPU v5e": V5E}


def spec_for_device_kind(device_kind: str) -> HardwareSpec:
    """The spec of the chip JAX reports as ``device_kind``.  A chip this
    module has no spec for is an error, never a silent V5E."""
    try:
        return _BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware spec for device_kind {device_kind!r} "
            f"(known: {sorted(_BY_DEVICE_KIND)})") from None

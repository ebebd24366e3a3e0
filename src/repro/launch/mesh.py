"""Production meshes.

Single pod: 16 x 16 = 256 chips (TPU v5e pod), axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis carries pure data parallelism across the DCN/ICI boundary.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run launcher must set XLA_FLAGS before any device query).
"""

from __future__ import annotations

import dataclasses

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """1x1 mesh for CPU tests/examples (same code path, no sharding)."""
    return jax.make_mesh((1, 1), ("data", "model"))


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind."""

    flops_bf16: float          # FLOP/s
    hbm_bw: float              # bytes/s
    ici_bw_per_link: float     # bytes/s per link, one direction
    hbm_bytes: float           # bytes


#: Peaks keyed by `jax.Device.device_kind`.  Source: Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s,
#: 1,600 Gbit/s of inter-chip interconnect (four links of 50 GB/s).
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9,
                             ici_bw_per_link=50e9, hbm_bytes=16e9),
}

#: The chip the production meshes above model (a v5e pod).
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peak table entry for `device_kind`; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak rates recorded for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None

"""Hardware ("system") descriptors for the exaCB-JAX fleet.

A *system* in the paper's sense (``jedi``, ``jureca``, ``jupiter``) maps to a
mesh topology plus per-chip roofline constants here.  The dry-run harness and
the roofline analysis consume these constants; the CPU container never
executes at these speeds — it only compiles against the topology.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-chip roofline constants."""

    name: str
    peak_flops_bf16: float   # FLOP/s
    hbm_bytes: float         # HBM capacity per chip
    hbm_bw: float            # bytes/s
    ici_bw_per_link: float   # bytes/s, one direction, one link
    ici_links: int           # ICI links per chip (torus degree)
    # Power model for the energy-injection feature (paper §VI-B, jpwr
    # analogue).  Simple affine model: P = idle + util_compute * c + util_mem * m.
    power_idle_w: float = 90.0
    power_peak_compute_w: float = 170.0   # additional W at 100% MXU util
    power_peak_hbm_w: float = 60.0        # additional W at 100% HBM util


# Published peaks of one TPU v5e chip (Google Cloud documentation, "TPU
# v5e"): 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI.
TPU_V5E = ChipSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bytes=16e9,
    hbm_bw=819e9,
    ici_bw_per_link=50e9,
    ici_links=4,
)

#: Peaks by ``jax.Device.device_kind``.  A device whose kind is missing has
#: no peaks: callers record no roofline terms for it rather than guess.
CHIPS_BY_KIND: Dict[str, ChipSpec] = {"TPU v5 lite": TPU_V5E}


@dataclasses.dataclass(frozen=True)
class SystemSpec:
    """A named system = chip model + mesh topology (the paper's 'machine')."""

    name: str
    chip: Optional[ChipSpec]   # None: no published peaks (the CPU host)
    mesh_shape: Tuple[int, ...]
    mesh_axes: Tuple[str, ...]
    # Cross-pod (data-center interconnect) bandwidth per chip, bytes/s.  Only
    # meaningful when a "pod" axis exists.
    dci_bw_per_chip: float = 6.25e9

    @property
    def n_chips(self) -> int:
        n = 1
        for s in self.mesh_shape:
            n *= s
        return n


SINGLE_POD = SystemSpec(
    name="v5e-pod-16x16",
    chip=TPU_V5E,
    mesh_shape=(16, 16),
    mesh_axes=("data", "model"),
)

MULTI_POD = SystemSpec(
    name="v5e-2pods-2x16x16",
    chip=TPU_V5E,
    mesh_shape=(2, 16, 16),
    mesh_axes=("pod", "data", "model"),
)

# One v5e host of four chips, all on the model axis: tensor parallelism for
# a model one chip cannot hold.
V5E_2X2 = SystemSpec(
    name="v5e-2x2",
    chip=TPU_V5E,
    mesh_shape=(1, 4),
    mesh_axes=("data", "model"),
)

# Reduced-scale system used by smoke tests and CPU execution benchmarks.
CPU_SMOKE = SystemSpec(
    name="cpu-smoke",
    chip=None,
    mesh_shape=(1, 1),
    mesh_axes=("data", "model"),
)

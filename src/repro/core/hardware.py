"""Hardware constants — the paper's resource vector, re-derived for TPU.

The paper (Sec. 2, Eq. 1) models an FPGA as a resource vector
``r_max = [LUTs, FFs, DSPs]`` plus ``N_b`` BRAM blocks of ``s_b`` words with
port width ``w_b``.  On TPU the analogous constants are: MXU throughput,
VMEM capacity (the fast memory ``S``), the (sublane, lane) tiling quantum
(the analog of the BRAM port-width granularity of Eq. 8), HBM bandwidth,
and ICI link bandwidth.  Everything downstream (tile solver, roofline,
distributed schedule choice) is parameterized over this dataclass, which is
what makes the implementation portable across TPU generations — the same
property the paper claims for its HLS code across FPGAs.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TpuTarget:
    """Hardware constants for one TPU chip + its interconnect.

    Defaults are one TPU v5e chip.  Published figures (Google Cloud
    documentation, "TPU v5e"): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB
    of HBM at 819 GB/s.
    """

    name: str = "tpu-v5e"

    # Compute: peak MAC throughput on the MXU.  fp32 is not published;
    # it runs as multiple bf16 passes, modeled here as 1/4 of bf16.
    peak_flops_bf16: float = 197e12
    peak_flops_fp32: float = 197e12 / 4
    peak_flops_int8: float = 393e12

    # Memory tiers.
    vmem_bytes: int = 128 * 1024 * 1024  # fast memory "S" of the paper
    hbm_bytes: int = 16 * 1024 * 1024 * 1024
    hbm_bandwidth: float = 819e9  # B/s

    # Interconnect. ~50 GB/s per ICI link (v5e: 4 links per chip in a
    # 2D torus); DCN between pods is far slower — modeled separately so the
    # 2.5D schedule can weight pod-axis traffic.
    ici_bandwidth: float = 50e9  # B/s per link (spec-mandated constant)
    ici_links: int = 4
    dcn_bandwidth: float = 6.25e9  # B/s per host (50 Gb/s), pod axis

    # MXU geometry: 128x128 systolic array. The analog of the paper's
    # "compute tile must be evaluated every cycle".
    mxu_dim: int = 128

    # VREG/VPU lane geometry: native tiling is (sublane, lane) =
    # (8, 128) for 32-bit types; narrower types pack 2x/4x sublanes.
    lane: int = 128
    sublane: int = 8

    def peak_flops(self, dtype) -> float:
        dtype = jnp.dtype(dtype)
        if dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)):
            return self.peak_flops_bf16
        if dtype in (jnp.dtype(jnp.int8), jnp.dtype(jnp.uint8)):
            return self.peak_flops_int8
        return self.peak_flops_fp32

    def sublane_tile(self, dtype) -> Tuple[int, int]:
        """Native (second-minor, minor) tile for ``dtype``.

        This is the TPU analog of the paper's Eq. 8 port-width quantum
        ``N_b,min``: block shapes that are not multiples of this tile waste
        fast-memory ports (here: padded VREG lanes).
        """
        itemsize = jnp.dtype(dtype).itemsize
        packing = max(1, 4 // itemsize)  # 32-bit:1, 16-bit:2, 8-bit:4
        return (self.sublane * packing, self.lane)

    def matmul_flops_per_sec(self, dtype) -> float:
        return self.peak_flops(dtype)


# Default production target used throughout the repo.
V5E = TpuTarget()

# TPU v5p (Google Cloud documentation, "TPU v5p"): 459 TFLOP/s bf16,
# 918 TOP/s int8, 95 GB of HBM at 2765 GB/s.
V5P = TpuTarget(
    name="tpu-v5p",
    peak_flops_bf16=459e12,
    peak_flops_fp32=459e12 / 4,
    peak_flops_int8=918e12,
    vmem_bytes=128 * 1024 * 1024,
    hbm_bytes=95 * 1024 * 1024 * 1024,
    hbm_bandwidth=2765e9,
    ici_bandwidth=100e9,
    ici_links=6,
)

# The peak table, keyed by the ``device_kind`` JAX reports for the chip.
TARGETS: Dict[str, TpuTarget] = {"TPU v5 lite": V5E, "TPU v5": V5P}


def target_for_kind(device_kind: str) -> TpuTarget:
    """The target of a chip by its ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return TARGETS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table entry for device kind {device_kind!r} "
            f"(known: {sorted(TARGETS)})") from None


def target_for_device(device=None) -> TpuTarget:
    """The target of ``device`` (default: the first JAX device)."""
    if device is None:
        device = jax.devices()[0]
    return target_for_kind(device.device_kind)

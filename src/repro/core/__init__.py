"""Core: the paper's contribution — model-driven communication-avoiding
matrix multiplication — as a composable JAX module."""

from repro.core.hardware import (TpuTarget, V5E, V5P, target_for_device,
                                  target_for_kind)
from repro.core.io_model import (
    TileConfig,
    arithmetic_intensity_ops_per_byte,
    computational_intensity,
    epilogue_q_elements,
    gemm_roofline,
    io_lower_bound_elements,
    io_volume_bytes,
    io_volume_elements,
    io_volume_elements_program,
    solve_tile_config,
    two_pass_glu_q_elements,
    vmem_quantum,
)
from repro.core.gemm import (
    ca_einsum, ca_expert_glu_matmul, ca_expert_matmul, ca_glu_matmul,
    ca_matmul, dist_local_matmul, gemm_fallback, gemm_fallback_enabled,
    gemm_mode, get_gemm_mode, plan_for, set_gemm_fallback, set_gemm_mode,
)
from repro.kernels.epilogue import Epilogue, EpilogueSpec
from repro.kernels.program import GemmProgramSpec, PrologueSpec, RmsPrologue
from repro.core.distributed import (
    SCHEDULES,
    DistributedCost,
    choose_schedule,
    dist_local_resolution,
    dist_local_shapes,
    dist_matmul,
    dist_matmul_reference,
    estimate_cost,
)

__all__ = [
    "TpuTarget", "V5E", "V5P", "target_for_device", "target_for_kind",
    "TileConfig", "computational_intensity", "arithmetic_intensity_ops_per_byte",
    "io_volume_elements", "io_volume_bytes", "io_lower_bound_elements",
    "io_volume_elements_program", "two_pass_glu_q_elements",
    "solve_tile_config",
    "vmem_quantum", "gemm_roofline", "epilogue_q_elements",
    "ca_matmul", "ca_glu_matmul", "ca_expert_matmul", "ca_expert_glu_matmul",
    "ca_einsum", "dist_local_matmul", "gemm_mode", "get_gemm_mode",
    "set_gemm_mode",
    "gemm_fallback", "gemm_fallback_enabled", "set_gemm_fallback",
    "plan_for", "Epilogue", "EpilogueSpec",
    "GemmProgramSpec", "PrologueSpec", "RmsPrologue",
    "SCHEDULES", "DistributedCost", "choose_schedule",
    "dist_local_resolution", "dist_local_shapes", "dist_matmul",
    "dist_matmul_reference", "estimate_cost",
]

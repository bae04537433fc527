"""Paper-equation tests: Eq. 3/5/6/7/8/9 adapted to TPU constants."""

import jax.numpy as jnp
import math
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (V5E, computational_intensity, io_lower_bound_elements,
                        io_volume_elements, solve_tile_config, vmem_quantum)
from repro.core.io_model import tile_vmem_bytes


def test_intensity_square_optimal():
    # Eq. 7: for fixed perimeter budget, square maximizes intensity.
    assert computational_intensity(512, 512) > computational_intensity(256, 768)
    assert computational_intensity(512, 512) > computational_intensity(768, 256)


def test_io_volume_matches_paper_form():
    # Eq. 6: Q = mn (1 + k (1/x + 1/y))
    m = n = k = 4096
    q = io_volume_elements(m, n, k, 512, 512)
    assert q == m * n * (1 + k * (2 / 512))


def test_lower_bound_dominates():
    m = n = k = 8192
    s_words = V5E.vmem_bytes // 4
    lb = io_lower_bound_elements(m, n, k, s_words)
    # any feasible square tile respects the bound
    for t in (256, 512, 1024, 2048):
        assert io_volume_elements(m, n, k, t, t) >= lb * 0.5  # tile <= sqrt(S)


def test_quantum_packing():
    assert vmem_quantum(jnp.float32) == (8, 128)
    assert vmem_quantum(jnp.bfloat16) == (16, 128)
    assert vmem_quantum(jnp.int8) == (32, 128)


@settings(max_examples=40, deadline=None)
@given(
    m=st.integers(128, 1 << 15),
    n=st.integers(128, 1 << 15),
    k=st.integers(128, 1 << 15),
    dt=st.sampled_from(["bfloat16", "float32", "int8"]),
)
def test_solver_properties(m, n, k, dt):
    dtype = jnp.dtype(dt)
    t = solve_tile_config(m, n, k, dtype_in=dtype)
    qm, qn = vmem_quantum(dtype)
    # hardware-legal (Eq. 8 analog)
    assert t.bm % qm == 0 and t.bn % qn == 0 and t.bk % 128 == 0
    # capacity constraint (Eq. 5)
    assert t.vmem_bytes <= 0.75 * V5E.vmem_bytes + 1
    # consistency of the accounting
    acc = 4 if dt != "int8" else 4
    assert t.vmem_bytes == tile_vmem_bytes(t.bm, t.bn, t.bk,
                                           dtype.itemsize, acc)


def test_solver_prefers_square_when_unconstrained():
    t = solve_tile_config(1 << 16, 1 << 16, 1 << 16, dtype_in=jnp.float32)
    assert 0.5 <= t.bm / t.bn <= 2.0


def test_drain_separation_beats_double_buffer():
    # Sec. 4.4: double-buffering the output tile costs ~sqrt(2) intensity.
    t_ours = solve_tile_config(1 << 15, 1 << 15, 1 << 15,
                               dtype_in=jnp.float32)
    t_db = solve_tile_config(1 << 15, 1 << 15, 1 << 15,
                             dtype_in=jnp.float32, double_buffer_out=True)
    assert t_ours.intensity > t_db.intensity
    # approaches sqrt(2) up to quantization slop (Eq. 9)
    assert t_ours.intensity / t_db.intensity > 1.15


def test_burst_penalty_boundary():
    from repro.core.io_model import burst_penalty, effective_intensity

    assert burst_penalty(256, 2) == 1.0          # 512B rows: full speed
    assert burst_penalty(128, 2) == 2.0          # 256B rows: 2x traffic
    assert burst_penalty(128, 4) == 1.0          # fp32 ok at bk=128
    # effective intensity折 halves when the burst penalty doubles
    assert (effective_intensity(1024, 1024, 128, 2)
            == 0.5 * effective_intensity(1024, 1024, 256, 2) * (1.0)) or True
    e1 = effective_intensity(1024, 1024, 256, 2)
    e2 = effective_intensity(1024, 1024, 128, 2)
    assert abs(e2 - e1 / 2) < 1e-9


def test_solver_burst_aware_bk():
    import jax.numpy as jnp
    from repro.core import solve_tile_config

    t_bf16 = solve_tile_config(16384, 16384, 16384, dtype_in=jnp.bfloat16)
    assert t_bf16.bk * 2 >= 512          # >= one HBM transaction per row
    t_int8 = solve_tile_config(16384, 16384, 16384, dtype_in=jnp.int8)
    assert t_int8.bk * 1 >= 512


@pytest.mark.parametrize("kind, name", [("TPU v5 lite", "tpu-v5e"),
                                        ("TPU v5", "tpu-v5p")])
def test_target_resolves_from_device_kind(kind, name):
    from repro.core import target_for_kind

    assert target_for_kind(kind).name == name


def test_unknown_device_kind_raises():
    from repro.core import target_for_kind

    with pytest.raises(ValueError, match="no peak table entry"):
        target_for_kind("TPU v9 imaginary")


def test_kernel_vmem_limit_covers_plan_and_caps_at_vmem():
    from repro.core.io_model import kernel_vmem_limit_bytes

    planned = tile_vmem_bytes(512, 1024, 512, 2)
    limit = kernel_vmem_limit_bytes(planned, 512, 1024)
    assert planned + 512 * 1024 * 4 < limit <= V5E.vmem_bytes
    assert kernel_vmem_limit_bytes(V5E.vmem_bytes, 512, 1024) == \
        V5E.vmem_bytes

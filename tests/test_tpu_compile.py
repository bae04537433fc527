"""Ahead-of-time compiles of the serve path's kernels for a TPU v5e chip,
at StableLM-2-1.6B's widths, with no chip attached.

The TPU compiler refuses what interpret mode accepts: block shapes off
the (8, 128) tiling and kernels over Mosaic's scoped-VMEM limit.  Each
test compiles one kernel through its dispatch path for a described
``v5e:2x2`` topology and checks that the Pallas kernel is in the
program.  The topology is described inside a fixture, so only the
worker that runs this file loads the TPU library, and the tests skip
where it cannot be described.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import io_model
from repro.core.gemm import ca_glu_matmul, ca_matmul
from repro.kernels.program import RmsPrologue
from repro.quant.scales import QTensor

D_MODEL, D_FF, HEADS, HEAD_DIM = 2048, 5632, 32, 64   # StableLM-2-1.6B
DECODE_M, PREFILL_M = 8, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # repro: noqa RPR004 -- no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off
    (its entries cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _glu_decode(one_chip):
    x = _sds(one_chip, (DECODE_M, D_MODEL), jnp.bfloat16)
    w = _sds(one_chip, (D_MODEL, D_FF), jnp.bfloat16)
    gain = _sds(one_chip, (D_MODEL,), jnp.bfloat16)
    return (lambda x, wg, wu, g: ca_glu_matmul(
        x, wg, wu, prologue=RmsPrologue(gain=g, eps=1e-5), mode="pallas"),
        x, w, w, gain)


def test_dense_gemm_decode_width(one_chip):
    x = _sds(one_chip, (DECODE_M, D_MODEL), jnp.bfloat16)
    w = _sds(one_chip, (D_MODEL, D_FF), jnp.bfloat16)
    _compile(lambda x, w: ca_matmul(x, w, mode="pallas"), x, w)


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M],
                         ids=["decode", "prefill"])
def test_rms_glu_program(one_chip, m):
    """``rms>glu.silu(none|none)``: the dense FFN's gate/up program."""
    fn, _, w, _, gain = _glu_decode(one_chip)
    x = _sds(one_chip, (m, D_MODEL), jnp.bfloat16)
    _compile(fn, x, w, w, gain)


def test_int8_weight_dqb_decode_width(one_chip):
    x = _sds(one_chip, (DECODE_M, D_MODEL), jnp.bfloat16)
    data = _sds(one_chip, (D_MODEL, D_FF), jnp.int8)
    scale = _sds(one_chip, (1, D_FF), jnp.float32)
    _compile(lambda x, d, s: ca_matmul(x, QTensor(data=d, scale=s),
                                       mode="pallas"), x, data, scale)


@pytest.mark.parametrize("seq_len", [144, 4096])
def test_paged_decode_kernel(one_chip, seq_len):
    from repro.kernels.flash_attn import paged_flash_attention_tpu
    from repro.tuning import resolve_page_size

    page = resolve_page_size(heads=HEADS, kv_heads=HEADS, head_dim=HEAD_DIM,
                             seq_len=seq_len).config.kv_block
    B, NP = 4, -(-seq_len // page)
    pool = _sds(one_chip, (B * NP, HEADS, page, HEAD_DIM), jnp.int8)
    scales = _sds(one_chip, (B * NP,), jnp.float32)
    _compile(paged_flash_attention_tpu,
             _sds(one_chip, (B, HEADS, HEAD_DIM), jnp.bfloat16), pool, pool,
             scales, scales, _sds(one_chip, (B, NP), jnp.int32),
             _sds(one_chip, (B,), jnp.int32))


def test_vmem_plan_brackets_compiler_need(one_chip, monkeypatch):
    """The kernel's planned VMEM bytes (the solver's count) against the
    compiler: at 90% of the planned bytes Mosaic refuses the decode GLU
    program, at the planned bytes plus headroom it accepts it (the
    ordinary compile above)."""
    planned = []
    real = io_model.kernel_vmem_limit_bytes

    def short_limit(bytes_, bm, bn, hw=io_model.V5E):
        planned.append(bytes_)
        assert real(bytes_, bm, bn, hw) >= bytes_
        return int(0.9 * bytes_)

    monkeypatch.setattr(io_model, "kernel_vmem_limit_bytes", short_limit)
    fn, *args = _glu_decode(one_chip)
    with pytest.raises(Exception, match="(?i)vmem"):
        jax.jit(fn).lower(*args).compile()
    assert planned and planned[0] > 4 * 1024 * 1024

"""Flash attention as a Pallas TPU kernel — the paper's I/O-minimal tiling
applied to the attention CDAG (beyond-paper extension, EXPERIMENTS §Perf).

Motivation from the dry-run roofline: the pure-JAX chunked attention in
``models/attention.py`` materializes every (q-chunk, kv-chunk) score tile
as an XLA intermediate; tiles larger than VMEM round-trip HBM, which the
HLO byte accounting shows dominating the memory term of every *_4k/32k
cell.  This kernel holds the running max/denominator and the output
accumulator in VMEM scratch across the kv grid dimension — the exact
output-stationary/drain-phase structure of the CA-MMM kernel, so score
tiles NEVER touch HBM:

  per (batch*kv_head, q_block) output tile:
      HBM reads  = q block once + k/v streamed once
      HBM writes = output block once (drain at last kv step)

Supports causal masking, sliding windows (rolling-cache positions come in
as explicit position arrays), and GQA (G query heads share one kv head by
folding G into the q-block rows).  Oracle: ``ref.ref_flash_attention``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _fa_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
               acc_ref, m_ref, l_ref, *, causal: bool,
               window: Optional[int], scale: float, kc: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                      # (G*qc, D)
    k = k_ref[0]                      # (kc, D)
    v = v_ref[0]                      # (kc, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (G*qc, kc)

    qpos = qpos_ref[0]                # (G*qc,) int32 (G-tiled q positions)
    kpos = kpos_ref[0]                # (kc,) int32; -1 = invalid slot
    mask = (kpos >= 0)[None, :]
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _drain():
        # Paper Sec. 4.4: single write-back of the output tile.
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_tpu(
    q: jax.Array,                 # (B, Lq, H, D)
    k: jax.Array,                 # (B, S, Hkv, D)
    v: jax.Array,                 # (B, S, Hkv, D)
    *,
    q_positions: jax.Array,       # (B, Lq) int32
    kv_positions: jax.Array,      # (B, S) int32, -1 = invalid
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_block: Optional[int] = None,
    kv_block: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    B, Lq, H, D = q.shape
    _, S, Hkv, Dv = v.shape
    G = H // Hkv
    scale = D ** -0.5 if scale is None else scale

    if q_block is None or kv_block is None:
        # Block sizes resolve through the kernel-config registry (cache >
        # autotune > analytic), like every GEMM tile in the repo.
        from repro.tuning.attention import resolve_attention  # lazy cycle
        cfg = resolve_attention("flash", heads=H, kv_heads=Hkv, head_dim=D,
                                seq_len=S, kv_dtype=k.dtype).config
        q_block = q_block or cfg.q_block
        kv_block = kv_block or cfg.kv_block

    qc = min(q_block, Lq)
    kc = min(kv_block, S)
    pad_q = (-Lq) % qc
    pad_k = (-S) % kc
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad_q)),
                              constant_values=-(10 ** 9))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad_k)),
                               constant_values=-1)
    Lp, Sp = q.shape[1], k.shape[1]
    nq, nk = Lp // qc, Sp // kc

    # (B*Hkv, G*L, D) layout: G query heads fold into the q rows so each
    # grid cell is a plain (G*qc, D) x (D, kc) MXU product.  Rows are
    # ordered q-block-major — (nq, G, qc) per head — so one grid q-step
    # sees all G heads of its q block.
    qr = q.reshape(B, nq, qc, Hkv, G, D).transpose(0, 3, 1, 4, 2, 5) \
          .reshape(B * Hkv, nq * G * qc, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sp, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sp, Dv)
    qpos_r = jnp.repeat(
        q_positions.reshape(B, nq, 1, qc), G, axis=2) \
        .reshape(B, 1, nq * G * qc)
    qpos_r = jnp.broadcast_to(qpos_r, (B, Hkv, nq * G * qc)) \
        .reshape(B * Hkv, nq * G * qc)
    kpos_r = jnp.broadcast_to(kv_positions[:, None, :], (B, Hkv, Sp)) \
        .reshape(B * Hkv, Sp)

    grid = (B * Hkv, nq, nk)
    kernel = functools.partial(_fa_kernel, causal=causal, window=window,
                               scale=scale, kc=kc)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G * qc), lambda b, i, j: (b, i)),      # qpos
            pl.BlockSpec((1, kc), lambda b, i, j: (b, j)),          # kpos
            pl.BlockSpec((1, G * qc, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kc, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kc, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G * qc, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, nq * G * qc, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * qc, Dv), jnp.float32),
            pltpu.VMEM((G * qc,), jnp.float32),
            pltpu.VMEM((G * qc,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qpos_r, kpos_r, qr, kr, vr)

    out = out.reshape(B, Hkv, nq, G, qc, Dv).transpose(0, 2, 4, 1, 3, 5) \
             .reshape(B, nq * qc, H, Dv)
    return out[:, :Lq]


# ---------------------------------------------------------------------------
# Paged int8 decode attention (repro.kvcache's kernel entry point)
# ---------------------------------------------------------------------------

def _paged_fa_kernel(tables_ref, lens_ref, ksc_ref, vsc_ref, q_ref,
                     k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                     page: int, n_kv: int, window: Optional[int],
                     scale: float):
    """One (batch*kv_head, page-step) cell of paged decode attention.

    The kv grid dimension streams int8 KV *pages* (gathered by the
    scalar-prefetched block table) through the same output-stationary
    running-softmax accumulate as :func:`_fa_kernel`; the per-page fp32
    dequant scales ride the kv step exactly like per-tile ``dqb``
    b-scales ride a quantized GEMM's k-step — applied to the partial
    scores / partial PV product in VMEM, so the dequantized K/V never
    exist in HBM.  Scales and lengths are scalar-prefetch (SMEM) reads.
    """
    bh = pl.program_id(0)
    j = pl.program_id(1)
    b = bh // n_kv

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                       # (G, D) serve dtype
    k = k_ref[0, 0]                    # (page, D) int8 payload
    v = v_ref[0, 0]                    # (page, Dv) int8 payload
    ksc = ksc_ref[b, j]                # this page's fp32 scales
    vsc = vsc_ref[b, j]
    # Dequant fused into the score accumulate: the int8 page contracts
    # directly and the page scale folds into the softmax logit scale.
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * (scale * ksc)  # (G, page)

    seq_len = lens_ref[b]
    qpos = seq_len - 1                 # the decode token is the newest
    kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    mask = kpos < seq_len              # causal + ragged tail + unmapped
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]                # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    # PV on the int8 page, the page's v-scale riding the partial product.
    pv = jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * vsc
    acc_ref[...] = acc_ref[...] * alpha + pv
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _drain():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_flash_attention_tpu(
    q: jax.Array,                 # (B, H, D) — one decode token per seq
    k_pages: jax.Array,           # (P, Hkv, page, D) int8
    v_pages: jax.Array,           # (P, Hkv, page, Dv) int8
    k_scale: jax.Array,           # (P,) fp32 per-page scales
    v_scale: jax.Array,           # (P,) fp32
    block_tables: jax.Array,      # (B, NP) int32 page ids; -1 = unmapped
    seq_lens: jax.Array,          # (B,) int32 tokens present per sequence
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention streaming int8 KV pages via a block table.

    The block table is a **scalar-prefetch** operand
    (:class:`pltpu.PrefetchScalarGridSpec`): page ids are available
    before the kernel body runs, so the K/V ``index_map`` gathers page
    ``tables[b, j]`` of the pool for kv step ``j`` — the PagedAttention
    layout under the paper's single-drain kernel structure.  Pages are
    stored head-major, so one grid step streams one kv head's
    ``(page, D)`` tile: a block whose two minor dims are the array's own,
    which the TPU lowering accepts for any page size and head dim.
    Positions are implicit (token ``t`` of page step ``j`` sits at
    ``j*page + t``), so ragged lengths, partially-filled tail pages and
    unmapped table slots all mask through one ``kpos < seq_len``
    predicate.  Returns ``(B, H, Dv)`` in ``q.dtype``.
    """
    B, H, D = q.shape
    P, Hkv, page, Dv = v_pages.shape
    G = H // Hkv
    NP = block_tables.shape[1]
    scale = D ** -0.5 if scale is None else scale

    qr = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    tables = jnp.maximum(block_tables, 0).astype(jnp.int32)
    # Per-(seq, page-step) scale planes: scales ride the kv grid like the
    # quantized GEMM's per-tile b-scales ride the k grid.
    ksc = k_scale[tables]              # (B, NP) fp32
    vsc = v_scale[tables]

    def page_map(bh, j, t, lens, ks, vs):
        return (t[bh // Hkv, j], bh % Hkv, 0, 0)

    grid = (B * Hkv, NP)
    kernel = functools.partial(_paged_fa_kernel, page=page, n_kv=Hkv,
                               window=window, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # block table, seq lens, k scales, v scales
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, G, D), lambda bh, j, *_: (bh, 0, 0)),
                pl.BlockSpec((1, 1, page, D), page_map),
                pl.BlockSpec((1, 1, page, Dv), page_map),
            ],
            out_specs=pl.BlockSpec((1, G, Dv), lambda bh, j, *_: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, Dv), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_flash_attention",
    )(tables, seq_lens.astype(jnp.int32), ksc, vsc, qr, k_pages, v_pages)
    return out.reshape(B, Hkv, G, Dv).reshape(B, H, Dv)

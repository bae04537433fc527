"""Attention: GQA/MQA, sliding-window, MLA (multi-head latent attention),
with a chunked online-softmax ("flash") implementation in pure JAX.

The chunked attention is the paper's I/O argument applied to attention:
the (Lq, S) score matrix is never materialized — scores are produced and
consumed per (q-chunk, kv-chunk) tile while running statistics (m, l) and
the output accumulator stay resident, mirroring the output-stationary
C-tile of the CA-MMM kernel.  A Pallas version of the same schedule lives
in ``repro.kernels.flash_attn`` (beyond-paper extension).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.gemm import ca_matmul
from repro.kernels.epilogue import Epilogue
from repro import kvcache as kvc
from repro.models import common as cm
from repro.models.common import Defs, ParamDef
from repro.obs.trace import kernel_scope

NEG = -1e30


# ---------------------------------------------------------------------------
# Chunked (flash) attention — pure JAX oracle-grade implementation
# ---------------------------------------------------------------------------

def _pad_axis(x, mult, axis, value=0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@kernel_scope("attn")
def flash_attention(
    q: jax.Array,            # (B, Lq, H, Dq)
    k: jax.Array,            # (B, S, Hkv, Dq)
    v: jax.Array,            # (B, S, Hkv, Dv)
    *,
    q_positions: jax.Array,  # (B, Lq) int32
    kv_positions: jax.Array, # (B, S) int32; -1 marks invalid slots
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
) -> jax.Array:
    B, Lq, H, Dq = q.shape
    _, S, Hkv, _ = k.shape
    Dv = v.shape[-1]
    G = H // Hkv
    scale = Dq ** -0.5 if scale is None else scale
    dt = q.dtype

    qc = min(q_chunk, Lq)
    kc = min(kv_chunk, S)
    qp = _pad_axis(q, qc, 1)
    qpos = _pad_axis(q_positions, qc, 1, value=-(10 ** 9))
    kp = _pad_axis(k, kc, 1)
    vp = _pad_axis(v, kc, 1)
    kpos = _pad_axis(kv_positions, kc, 1, value=-1)
    nq, nk = qp.shape[1] // qc, kp.shape[1] // kc

    # (n, B, c, ...) chunk-major layouts for lax.scan.
    qs = qp.reshape(B, nq, qc, Hkv, G, Dq).transpose(1, 0, 2, 3, 4, 5)
    qps = qpos.reshape(B, nq, qc).transpose(1, 0, 2)
    ks = kp.reshape(B, nk, kc, Hkv, Dq).transpose(1, 0, 2, 3, 4)
    vs = vp.reshape(B, nk, kc, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    kps = kpos.reshape(B, nk, kc).transpose(1, 0, 2)

    def q_step(_, qx):
        q_i, qpos_i = qx  # (B, qc, Hkv, G, Dq), (B, qc)

        def kv_step(carry, kx):
            m, l, acc = carry
            k_j, v_j, kpos_j = kx
            # Scores on the MXU path: bf16 inputs, fp32 accumulation.
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            mask = kpos_j[:, None, :] >= 0
            if causal:
                mask &= kpos_j[:, None, :] <= qpos_i[:, :, None]
            if window is not None:
                mask &= kpos_j[:, None, :] > qpos_i[:, :, None] - window
            s = jnp.where(mask[:, None, None, :, :], s, NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(mask[:, None, None, :, :], p, 0.0)
            alpha = jnp.exp(m - m_new)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(dt), v_j,
                            preferred_element_type=jnp.float32)
            acc = acc * alpha[..., None] + pv
            l = l * alpha + p.sum(axis=-1)
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hkv, G, qc), NEG, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qc), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, qc, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (ks, vs, kps))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(dt)  # (B, Hkv, G, qc, Dv)

    _, outs = jax.lax.scan(q_step, None, (qs, qps))
    # (nq, B, Hkv, G, qc, Dv) -> (B, nq, qc, Hkv, G, Dv) -> (B, L, H, Dv)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * qc, H, Dv)
    return out[:, :Lq]


@kernel_scope("attn")
def dense_attention(q, k, v, *, q_positions, kv_positions, causal=True,
                    window=None, scale=None) -> jax.Array:
    """Unchunked scores — used for decode (Lq == 1) and tiny smoke runs."""
    B, Lq, H, Dq = q.shape
    _, S, Hkv, _ = k.shape
    G = H // Hkv
    scale = Dq ** -0.5 if scale is None else scale
    qf = q.reshape(B, Lq, Hkv, G, Dq)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k,
                   preferred_element_type=jnp.float32) * scale
    mask = kv_positions[:, None, :] >= 0
    if causal:
        mask &= kv_positions[:, None, :] <= q_positions[:, :, None]
    if window is not None:
        mask &= kv_positions[:, None, :] > q_positions[:, :, None] - window
    s = jnp.where(mask[:, None, None, :, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[:, None, None, :, :], p, 0.0)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Lq, H, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache (rolling for sliding-window archs)
# ---------------------------------------------------------------------------

def make_kv_cache(B: int, cache_len: int, n_kv: int, dk: int, dv: int,
                  dtype) -> Dict[str, jax.Array]:
    return {
        "k": jnp.zeros((B, cache_len, n_kv, dk), dtype),
        "v": jnp.zeros((B, cache_len, n_kv, dv), dtype),
        "pos": jnp.full((B, cache_len), -1, jnp.int32),
    }


def cache_len_for(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(cfg.sliding_window, seq_len)
    return seq_len


@kernel_scope("kv_write")
def kv_cache_insert(cache, k_new, v_new, step: jax.Array):
    """Insert one token (B, 1, Hkv, D) at rolling slot ``step % C``."""
    C = cache["k"].shape[1]
    slot = jnp.mod(step, C)
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new, slot, axis=1)
    cache["v"] = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new, slot, axis=1)
    pos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], jnp.broadcast_to(step, (cache["pos"].shape[0], 1)
                                       ).astype(jnp.int32), slot, axis=1)
    cache["pos"] = pos
    return cache


@kernel_scope("kv_write")
def kv_cache_from_prefill(k, v, positions, cache_len: int):
    """Build a cache from full-sequence prefill k/v.

    Keeps the last ``cache_len`` entries (rolling window) or zero-pads up
    to ``cache_len`` free slots (pos = -1) for later decode steps."""
    S = k.shape[1]
    positions = positions.astype(jnp.int32)
    if S > cache_len:
        k, v = k[:, -cache_len:], v[:, -cache_len:]
        positions = positions[:, -cache_len:]
    elif S < cache_len:
        pad = cache_len - S
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        positions = jnp.pad(positions, ((0, 0), (0, pad)),
                            constant_values=-1)
    return {"k": k, "v": v, "pos": positions}


# ---------------------------------------------------------------------------
# GQA / MQA attention layer
# ---------------------------------------------------------------------------

def gqa_defs(cfg: ModelConfig, depth_scale: float = 1.0) -> Defs:
    d = cfg.d_model
    Dh = cfg.resolved_head_dim
    return {
        "wq": ParamDef((d, cfg.n_heads * Dh), ("embed", "qkv")),
        "wk": ParamDef((d, cfg.n_kv_heads * Dh), ("embed", "qkv")),
        "wv": ParamDef((d, cfg.n_kv_heads * Dh), ("embed", "qkv")),
        "wo": ParamDef((cfg.n_heads * Dh, d), ("qkv", "embed"),
                       scale=depth_scale),
    }


def gqa_apply(p, x, cfg: ModelConfig, *, positions, cache=None,
              step=None, mode: str = "train", max_len: int = None,
              residual=None):
    """mode: train | prefill (returns cache) | decode (uses+updates cache).

    ``residual`` (the block's pre-norm stream) is added inside the output
    projection's drain phase — the attention block's ``x + attn(...)``
    costs no extra HBM round trip over the GEMM's mandatory write-back.
    """
    B, L, d = x.shape
    Dh = cfg.resolved_head_dim
    H, Kv = cfg.n_heads, cfg.n_kv_heads
    dt = x.dtype
    q = ca_matmul(x, cm.wcast(p["wq"], dt)).reshape(B, L, H, Dh)
    k = ca_matmul(x, cm.wcast(p["wk"], dt)).reshape(B, L, Kv, Dh)
    v = ca_matmul(x, cm.wcast(p["wv"], dt)).reshape(B, L, Kv, Dh)

    rope_pos = positions if cfg.rope_kind == "rope" else positions
    q = cm.apply_rope(q, rope_pos, cfg.rope_theta,
                      cfg.mrope_sections if cfg.rope_kind == "mrope" else None)
    k = cm.apply_rope(k, rope_pos, cfg.rope_theta,
                      cfg.mrope_sections if cfg.rope_kind == "mrope" else None)

    pos2d = positions if positions.ndim == 2 else positions[..., 0]
    if mode == "decode":
        assert cache is not None and step is not None
        if kvc.is_paged(cache):
            # Paged path: append quantizes into the page pool, attention
            # streams int8 pages (fused-dequant kernel on TPU, gather
            # oracle elsewhere).  Positions are implicit in the block
            # table + length, so `step` goes unused.
            cache = kvc.paged_decode_insert(cache, k, v)
            out = kvc.paged_attention(q, cache, window=cfg.sliding_window)
        else:
            cache = kv_cache_insert(cache, k, v, step)
            out = dense_attention(
                q, cache["k"], cache["v"], q_positions=pos2d,
                kv_positions=cache["pos"], causal=True,
                window=cfg.sliding_window)
        new_cache = cache
    else:
        out = flash_attention(
            q, k, v, q_positions=pos2d, kv_positions=pos2d,
            causal=True, window=cfg.sliding_window,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
        new_cache = None
        if mode == "prefill":
            if cache is not None and kvc.is_paged(cache):
                # Bulk-insert into pre-assigned pages; the slab path below
                # instead *builds* its cache from scratch.
                new_cache = kvc.paged_prefill_insert(cache, k, v)
            else:
                C = cache_len_for(cfg, max_len or L)
                new_cache = kv_cache_from_prefill(k, v, pos2d, C)
    epi = Epilogue(residual=residual) if residual is not None else None
    y = ca_matmul(out.reshape(B, L, H * Dh), cm.wcast(p["wo"], dt),
                  epilogue=epi)
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V2 family, MiniCPM3)
# ---------------------------------------------------------------------------

def mla_defs(cfg: ModelConfig, depth_scale: float = 1.0) -> Defs:
    d = cfg.d_model
    m = cfg.mla
    H = cfg.n_heads
    qdim = m.qk_nope_dim + m.qk_rope_dim
    defs: Defs = {}
    if m.q_lora_rank:
        defs["wq_a"] = ParamDef((d, m.q_lora_rank), ("embed", "lora"))
        defs["q_norm"] = ParamDef((m.q_lora_rank,), ("lora",), init="ones")
        defs["wq_b"] = ParamDef((m.q_lora_rank, H * qdim), ("lora", "qkv"))
    else:
        defs["wq"] = ParamDef((d, H * qdim), ("embed", "qkv"))
    defs["wkv_a"] = ParamDef((d, m.kv_lora_rank + m.qk_rope_dim),
                             ("embed", "lora"))
    defs["kv_norm"] = ParamDef((m.kv_lora_rank,), ("lora",), init="ones")
    defs["wkv_b"] = ParamDef((m.kv_lora_rank,
                              H * (m.qk_nope_dim + m.v_head_dim)),
                             ("lora", "qkv"))
    defs["wo"] = ParamDef((H * m.v_head_dim, d), ("qkv", "embed"),
                          scale=depth_scale)
    return defs


def _mla_q(p, x, cfg, positions):
    B, L, _ = x.shape
    m = cfg.mla
    H = cfg.n_heads
    dt = x.dtype
    if m.q_lora_rank:
        cq = ca_matmul(x, cm.wcast(p["wq_a"], dt))
        cq = cm.rms_norm(cq, p["q_norm"], cfg.norm_eps)
        q = ca_matmul(cq, cm.wcast(p["wq_b"], dt))
    else:
        q = ca_matmul(x, cm.wcast(p["wq"], dt))
    q = q.reshape(B, L, H, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = cm.apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(p, x, cfg, positions):
    """Compressed KV stream: c_kv (B, L, r) and shared rotary key."""
    m = cfg.mla
    dt = x.dtype
    ckv = ca_matmul(x, cm.wcast(p["wkv_a"], dt))
    c, k_rope = ckv[..., :m.kv_lora_rank], ckv[..., m.kv_lora_rank:]
    c = cm.rms_norm(c, p["kv_norm"], cfg.norm_eps)
    k_rope = cm.apply_rope(k_rope[:, :, None, :], positions,
                           cfg.rope_theta)[:, :, 0]
    return c, k_rope


def mla_apply(p, x, cfg: ModelConfig, *, positions, cache=None, step=None,
              mode: str = "train", max_len: int = None, residual=None):
    """MLA with the compressed-KV cache.

    train/prefill: expand k_nope/v from c_kv and run flash attention.
    decode: **matrix-absorbed** path — queries are projected into the
    kv_lora space so attention runs directly against the compressed cache
    (never materializing per-head K/V for the whole history).  This is the
    paper's minimize-the-streamed-operand idea applied to the KV cache.
    """
    B, L, d = x.shape
    m = cfg.mla
    H = cfg.n_heads
    dt = x.dtype
    pos2d = positions if positions.ndim == 2 else positions[..., 0]

    q_nope, q_rope = _mla_q(p, x, cfg, pos2d)
    c_kv, k_rope = _mla_ckv(p, x, cfg, pos2d)
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5

    wkv_b = p["wkv_b"].astype(dt).reshape(
        m.kv_lora_rank, H, m.qk_nope_dim + m.v_head_dim)
    w_uk = wkv_b[..., :m.qk_nope_dim]    # (r, H, nope)
    w_uv = wkv_b[..., m.qk_nope_dim:]    # (r, H, v)

    if mode == "decode":
        assert cache is not None and step is not None
        # cache: {"c": (B, C, r), "k_rope": (B, C, rope), "pos": (B, C)}
        with jax.named_scope("kv_write"):
            C = cache["c"].shape[1]
            slot = jnp.mod(step, C)
            cache = dict(cache)
            cache["c"] = jax.lax.dynamic_update_slice_in_dim(
                cache["c"], c_kv, slot, axis=1)
            cache["k_rope"] = jax.lax.dynamic_update_slice_in_dim(
                cache["k_rope"], k_rope, slot, axis=1)
            cache["pos"] = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], jnp.broadcast_to(step, (B, 1)).astype(jnp.int32),
                slot, axis=1)
        with jax.named_scope("attn"):
            # Absorbed scores: q_nope -> lora space.
            q_abs = jnp.einsum("blhn,rhn->blhr", q_nope, w_uk,
                               preferred_element_type=jnp.float32).astype(dt)
            s = jnp.einsum("blhr,bsr->bhls", q_abs, cache["c"],
                           preferred_element_type=jnp.float32)
            s += jnp.einsum("blhn,bsn->bhls", q_rope, cache["k_rope"],
                            preferred_element_type=jnp.float32)
            s *= scale
            mask = (cache["pos"][:, None, :] >= 0) & (
                cache["pos"][:, None, :] <= pos2d[:, :, None])
            s = jnp.where(mask[:, None], s, NEG)
            pattn = jax.nn.softmax(s, axis=-1)
            pattn = jnp.where(mask[:, None], pattn, 0.0)
            o_c = jnp.einsum("bhls,bsr->blhr", pattn.astype(dt), cache["c"],
                             preferred_element_type=jnp.float32).astype(dt)
            out = jnp.einsum("blhr,rhv->blhv", o_c, w_uv,
                             preferred_element_type=jnp.float32).astype(dt)
        new_cache = cache
    else:
        kv = jnp.einsum("blr,rhn->blhn", c_kv,
                        wkv_b.reshape(m.kv_lora_rank, H,
                                      m.qk_nope_dim + m.v_head_dim),
                        preferred_element_type=jnp.float32).astype(dt)
        k_nope = kv[..., :m.qk_nope_dim]
        v = kv[..., m.qk_nope_dim:]
        k = jnp.concatenate(
            [k_nope,
             jnp.broadcast_to(k_rope[:, :, None, :],
                              (B, L, H, m.qk_rope_dim))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = flash_attention(
            q, k, v, q_positions=pos2d, kv_positions=pos2d, causal=True,
            scale=scale, q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
        new_cache = None
        if mode == "prefill":
            C = cache_len_for(cfg, max_len or L)
            pos_c = pos2d.astype(jnp.int32)
            if C > L:
                c_kv = jnp.pad(c_kv, ((0, 0), (0, C - L), (0, 0)))
                k_rope = jnp.pad(k_rope, ((0, 0), (0, C - L), (0, 0)))
                pos_c = jnp.pad(pos_c, ((0, 0), (0, C - L)),
                                constant_values=-1)
            elif C < L:
                c_kv, k_rope = c_kv[:, -C:], k_rope[:, -C:]
                pos_c = pos_c[:, -C:]
            new_cache = {"c": c_kv, "k_rope": k_rope, "pos": pos_c}
    epi = Epilogue(residual=residual) if residual is not None else None
    y = ca_matmul(out.reshape(B, L, H * m.v_head_dim), cm.wcast(p["wo"], dt),
                  epilogue=epi)
    return y, new_cache


def make_mla_cache(B: int, cache_len: int, cfg: ModelConfig, dtype):
    m = cfg.mla
    return {
        "c": jnp.zeros((B, cache_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((B, cache_len, m.qk_rope_dim), dtype),
        "pos": jnp.full((B, cache_len), -1, jnp.int32),
    }


def attn_defs(cfg: ModelConfig, depth_scale: float = 1.0) -> Defs:
    if cfg.attn_kind == "mla":
        return mla_defs(cfg, depth_scale)
    return gqa_defs(cfg, depth_scale)


def attn_apply(p, x, cfg: ModelConfig, **kw):
    if cfg.attn_kind == "mla":
        return mla_apply(p, x, cfg, **kw)
    return gqa_apply(p, x, cfg, **kw)


def make_attn_cache(B: int, cache_len: int, cfg: ModelConfig, dtype):
    if cfg.attn_kind == "mla":
        return make_mla_cache(B, cache_len, cfg, dtype)
    Dh = cfg.resolved_head_dim
    return make_kv_cache(B, cache_len, cfg.n_kv_heads, Dh, Dh, dtype)

"""Device-side paged KV cache: int8 page payloads + per-page fp32 scales.

One *layer-level* cache is the pytree

.. code-block:: python

    {"k":       (P, Hkv, page, D)  int8,   # page pool, K payload
     "v":       (P, Hkv, page, Dv) int8,
     "k_scale": (P,) float32,              # per-page absmax scales
     "v_scale": (P,) float32,
     "tables":  (B, NP) int32,             # block table; -1 = unmapped
     "len":     (B,)  int32}               # tokens present per sequence

The model stacks one of these per layer along a leading axis (exactly
like the slab caches), sharing the page *ids* across layers: page ``p``
of layer ``l`` lives at ``k[l, p]``, so one host-side allocation
(:class:`repro.kvcache.pool.PagePool`) covers the whole depth.  Pages are
head-major: each kv head's ``(page, D)`` tile is contiguous, which is
the block the decode kernel streams per grid step.

Quantization reuses the :mod:`repro.quant.scales` convention: int8
symmetric on [-127, 127], fp32 scales.  Prefill bulk-inserts whole pages
(one absmax scale per page); the decode append *requantizes* the touched
page under ``max(old_scale, |token|/127)`` — a VMEM-sized rescale of one
page, never a pool-wide pass.  A freshly assigned page has scale 0, so
the first append rescales its stale payload by ``0 / new_scale`` — prior
tenants' bytes are dead on arrival, which is what makes page reuse safe.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import kernel_scope

_EPS = 1e-12
_QMAX = 127.0  # symmetric int8 grid, repro.quant.scales._FMT_MAX["int8"]

PAGED_KEYS = ("k", "v", "k_scale", "v_scale", "tables", "len")


def is_paged(cache) -> bool:
    """A cache pytree is paged iff it carries a block table."""
    return isinstance(cache, dict) and "tables" in cache


def make_paged_cache(n_pages: int, page_size: int, n_kv: int, dk: int,
                     dv: int, batch: int, max_pages: int
                     ) -> Dict[str, jax.Array]:
    """One layer's empty paged cache (see module docstring for layout)."""
    return {
        "k": jnp.zeros((n_pages, n_kv, page_size, dk), jnp.int8),
        "v": jnp.zeros((n_pages, n_kv, page_size, dv), jnp.int8),
        "k_scale": jnp.zeros((n_pages,), jnp.float32),
        "v_scale": jnp.zeros((n_pages,), jnp.float32),
        "tables": jnp.full((batch, max_pages), -1, jnp.int32),
        "len": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Sequence assignment (host-driven, device-applied)
# ---------------------------------------------------------------------------

def _table_row(page_ids: Sequence[int], max_pages: int) -> jnp.ndarray:
    ids = np.asarray(list(page_ids), np.int32)
    assert ids.size <= max_pages, (ids.size, max_pages)
    row = np.full((max_pages,), -1, np.int32)
    row[:ids.size] = ids
    return jnp.asarray(row)


def model_assign_sequence(cache, b: int, page_ids: Sequence[int]):
    """Bind pool pages to batch slot ``b`` across every layer.

    Writes the block-table row, resets the sequence length, and zeroes
    the assigned pages' scales (all layers — the leading stacked axis
    broadcasts), which logically clears any prior tenant's payload.
    """
    lay = dict(cache["layers"])
    row = _table_row(page_ids, lay["tables"].shape[-1])
    lay["tables"] = lay["tables"].at[..., b, :].set(row)
    lay["len"] = lay["len"].at[..., b].set(0)
    if len(page_ids):
        ids = jnp.asarray(np.asarray(list(page_ids), np.int32))
        lay["k_scale"] = lay["k_scale"].at[..., ids].set(0.0)
        lay["v_scale"] = lay["v_scale"].at[..., ids].set(0.0)
    out = dict(cache)
    out["layers"] = lay
    return out


def model_release_sequence(cache, b: int):
    """Unmap batch slot ``b``'s block-table row (pages return to the host
    free list separately — the payload bytes are left as garbage, made
    unreachable here and re-zeroed by the next ``model_assign_sequence``)."""
    lay = dict(cache["layers"])
    lay["tables"] = lay["tables"].at[..., b, :].set(
        jnp.full((lay["tables"].shape[-1],), -1, jnp.int32))
    lay["len"] = lay["len"].at[..., b].set(0)
    out = dict(cache)
    out["layers"] = lay
    return out


# ---------------------------------------------------------------------------
# Inserts
# ---------------------------------------------------------------------------

@kernel_scope("kv_write")
def paged_prefill_insert(cache: Dict[str, jax.Array], k_new: jax.Array,
                         v_new: jax.Array) -> Dict[str, jax.Array]:
    """Bulk-insert a prefill's K/V into the sequence's mapped pages.

    ``k_new``/``v_new`` are ``(B, L, Hkv, D)`` in the serve dtype.  Each
    page quantizes independently under its own absmax scale (the
    per-page analog of :func:`repro.quant.scales.absmax_scale` with the
    page as the block); the ragged tail page zero-pads, and the padding
    never scores because attention masks ``kpos >= len``.  The first
    ``ceil(L / page)`` table slots of every row must be mapped — the
    engine allocates before prefilling.
    """
    B, L, Hkv, Dk = k_new.shape
    Dv = v_new.shape[-1]
    page = cache["k"].shape[2]
    npg = -(-L // page)
    pad = npg * page - L

    def quantize_pages(x, d):
        xf = x.astype(jnp.float32)
        if pad:
            xf = jnp.pad(xf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        xb = xf.reshape(B, npg, page, Hkv, d)
        amax = jnp.max(jnp.abs(xb), axis=(2, 3, 4))          # (B, npg)
        scale = jnp.maximum(amax, _EPS) / _QMAX
        q = jnp.clip(jnp.round(xb / scale[:, :, None, None, None]),
                     -_QMAX, _QMAX).astype(jnp.int8)
        q = q.transpose(0, 1, 3, 2, 4)                       # head-major
        return q.reshape(B * npg, Hkv, page, d), scale.reshape(B * npg)

    kq, ks = quantize_pages(k_new, Dk)
    vq, vs = quantize_pages(v_new, Dv)
    ids = cache["tables"][:, :npg].reshape(B * npg)
    out = dict(cache)
    out["k"] = cache["k"].at[ids].set(kq)
    out["v"] = cache["v"].at[ids].set(vq)
    out["k_scale"] = cache["k_scale"].at[ids].set(ks)
    out["v_scale"] = cache["v_scale"].at[ids].set(vs)
    out["len"] = jnp.full_like(cache["len"], L)
    return out


def _append_token(pool: jax.Array, scales: jax.Array, pid: jax.Array,
                  slot: jax.Array, tok: jax.Array):
    """Requantizing append of one ``(Hkv, D)`` token into page ``pid``.

    The page's new scale is ``max(old, |tok|/127)``; the existing int8
    payload rescales by ``old/new`` (identity when the token fits the
    old grid, and exactly 0 for a fresh page whose scale is 0 — stale
    bytes die here).  One page round-trips VMEM; the pool doesn't.
    """
    n_kv, page, d = pool.shape[1:]
    old = jax.lax.dynamic_slice(pool, (pid, 0, 0, 0), (1, n_kv, page, d))
    old_sc = scales[pid]
    tokf = tok.astype(jnp.float32)
    new_sc = jnp.maximum(old_sc, jnp.maximum(jnp.max(jnp.abs(tokf)),
                                             _EPS) / _QMAX)
    rescaled = jnp.clip(jnp.round(old.astype(jnp.float32)
                                  * (old_sc / new_sc)),
                        -_QMAX, _QMAX).astype(jnp.int8)
    tok_q = jnp.clip(jnp.round(tokf / new_sc), -_QMAX, _QMAX
                     ).astype(jnp.int8)
    pg = jax.lax.dynamic_update_slice(rescaled, tok_q[None, :, None],
                                      (0, 0, slot, 0))
    pool = jax.lax.dynamic_update_slice(pool, pg, (pid, 0, 0, 0))
    return pool, scales.at[pid].set(new_sc)


@kernel_scope("kv_write")
def paged_decode_insert(cache: Dict[str, jax.Array], k_new: jax.Array,
                        v_new: jax.Array) -> Dict[str, jax.Array]:
    """Append one decode token ``(B, 1, Hkv, D)`` per sequence.

    The target page/slot derives from the sequence length (``len //
    page``, ``len % page``) through the block table, so the caller never
    handles page ids — it allocated enough pages up front and the table
    routes the write.
    """
    page = cache["k"].shape[2]
    B = cache["tables"].shape[0]
    out = dict(cache)
    for b in range(B):  # B is static and small (the serve batch)
        pos = cache["len"][b]
        pid = cache["tables"][b, pos // page]
        slot = pos % page
        out["k"], out["k_scale"] = _append_token(
            out["k"], out["k_scale"], pid, slot, k_new[b, 0])
        out["v"], out["v_scale"] = _append_token(
            out["v"], out["v_scale"], pid, slot, v_new[b, 0])
    out["len"] = cache["len"] + 1
    return out


# ---------------------------------------------------------------------------
# Attention over the paged cache
# ---------------------------------------------------------------------------

def gather_kv(cache: Dict[str, jax.Array], dtype=jnp.float32):
    """Dequantize the mapped pages into contiguous ``(B, NP*page, Hkv, D)``
    K/V plus a ``(B, NP*page)`` position array (-1 beyond ``len``).

    This is the XLA oracle path: it *materializes* the dequantized cache
    (the exact HBM regression the fused kernel exists to avoid), which
    makes it the reference the kernel parity tests and the non-TPU serve
    path run against — mirroring ``QTensor.dequantize`` vs the ``dqb``
    drain stage.
    """
    B, NP = cache["tables"].shape
    page = cache["k"].shape[2]
    ids = jnp.maximum(cache["tables"], 0)
    k = (cache["k"][ids].astype(jnp.float32)
         * cache["k_scale"][ids][..., None, None, None])
    v = (cache["v"][ids].astype(jnp.float32)
         * cache["v_scale"][ids][..., None, None, None])
    S = NP * page
    # (B, NP, Hkv, page, D) -> token-major (B, NP * page, Hkv, D)
    k = k.transpose(0, 1, 3, 2, 4).reshape(B, S, k.shape[2],
                                           k.shape[4]).astype(dtype)
    v = v.transpose(0, 1, 3, 2, 4).reshape(B, S, v.shape[2],
                                           v.shape[4]).astype(dtype)
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    pos = jnp.where(pos < cache["len"][:, None], pos, -1)
    return k, v, pos


def _auto_mode() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


@kernel_scope("attn")
def paged_attention(q: jax.Array, cache: Dict[str, jax.Array], *,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    mode: Optional[str] = None,
                    interpret: Optional[bool] = None,
                    config_source: str = "analytic") -> jax.Array:
    """Decode attention of ``q`` (``(B, 1, H, D)``) against the paged
    cache; returns ``(B, 1, H, Dv)``.

    ``mode``: ``"pallas"`` streams int8 pages through
    :func:`repro.kernels.flash_attn.paged_flash_attention_tpu` (dequant
    fused into the running softmax); ``"xla"`` runs the gather/dequant
    oracle; default picks pallas on TPU backends.  Every dispatch is
    recorded in the obs ledger with its planned KV bytes (the
    ``BENCH_attn.json`` accounting).
    """
    n_pages, Hkv, page, Dv = cache["v"].shape
    NP = cache["tables"].shape[1]
    # KV005 preflight: q must be a single decode step and the cache
    # geometry GQA-compatible; memoized per (shape, page, heads).
    from repro.analyze.preflight import preflight_attn  # lazy: analyze is a leaf

    preflight_attn(q.shape, page, q.shape[-2] if q.ndim == 4 else 0, Hkv)
    B, _, H, D = q.shape
    mode = mode or _auto_mode()

    from repro.obs.ledger import get_ledger  # lazy: obs is a leaf

    get_ledger().record_attention(
        b=B, q_len=1, kv_len=NP * page, heads=H, kv_heads=Hkv,
        head_dim=D, v_head_dim=Dv, kv_dtype=cache["k"].dtype,
        q_dtype=q.dtype, mode=mode, tag="attn.paged_decode", page=page,
        config_source=config_source)

    if mode == "pallas":
        from repro.kernels.flash_attn import paged_flash_attention_tpu

        out = paged_flash_attention_tpu(
            q[:, 0], cache["k"], cache["v"], cache["k_scale"],
            cache["v_scale"], cache["tables"], cache["len"],
            window=window, scale=scale,
            interpret=bool(interpret) if interpret is not None else False)
        return out[:, None]

    from repro.models.attention import dense_attention  # lazy cycle

    k, v, kv_pos = gather_kv(cache, dtype=q.dtype)
    q_pos = (cache["len"][:, None] - 1).astype(jnp.int32)
    return dense_attention(q, k, v, q_positions=q_pos, kv_positions=kv_pos,
                           causal=True, window=window, scale=scale)


def pages_for(n_tokens: int, page_size: int) -> int:
    """Host-side ceil helper shared with :class:`repro.kvcache.pool.PagePool`."""
    return -(-max(0, int(n_tokens)) // int(page_size))

"""Attention blocking through the kernel-config registry.

The GEMM registry's contract — cache > autotune > analytic, persistent
winners, one choke point for every dispatch — extends here to the two
attention kernels:

* ``arch="flash"``  — :func:`repro.kernels.flash_attn.flash_attention_tpu`;
  the tunables are the q/kv grid block sizes.
* ``arch="paged_decode"`` — the paged int8 decode kernel
  (:func:`~repro.kernels.flash_attn.paged_flash_attention_tpu`); the kv
  block *is* the page size (one grid step streams one page), so tuning
  it chooses the pool's page geometry and ``q_block`` degenerates to the
  single decode token.

Entries live in the same persistent :class:`repro.tuning.cache.TuningCache`
file as GEMM tiles, under keys that can't collide with GEMM keys (the
``attn.`` arch segment replaces the dtype/semiring fields).  A
:class:`~repro.tuning.cache.CacheEntry` stores ``bm=q_block``,
``bn=bk=kv_block``, ``order="attn"`` — the same schema, reinterpreted,
so the merge CLI and corruption handling need no changes.

Autotuning times the **real** kernel variant (the paged int8 kernel on a
synthetic pool, the flash kernel on causal bf16 inputs), interpreted off
TPU exactly like :func:`repro.tuning.autotune.time_tile` does for GEMMs.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hardware import TpuTarget
from repro.tuning.autotune import _auto_interpret
from repro.tuning.cache import CacheEntry, shape_bucket

_ORDER_TAG = "attn"          # CacheEntry.order marker for attention entries
_TUNE_WARMUP = 1
_TUNE_ITERS = 3

# Lane-aligned page candidates; 16 keeps tiny-context pools from wasting
# 8x their payload, 256 caps the per-grid-step VMEM slice.
_PAGE_CANDIDATES = (16, 32, 64, 128, 256)
_FLASH_Q = (128, 256, 512)
_FLASH_KV = (128, 256, 512, 1024)


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    """Resolved attention blocking.  For ``paged_decode``, ``kv_block``
    is the page size and ``q_block`` is vestigial (decode q_len is 1)."""

    q_block: int
    kv_block: int

    def to_entry(self, *, measured_s: float = 0.0, n_tried: int = 0,
                 source: str = "autotune") -> CacheEntry:
        return CacheEntry(bm=self.q_block, bn=self.kv_block,
                          bk=self.kv_block, order=_ORDER_TAG,
                          measured_s=measured_s, n_tried=n_tried,
                          source=source, updated_at=time.time())

    @staticmethod
    def from_entry(entry: CacheEntry) -> "AttnConfig":
        return AttnConfig(q_block=entry.bm, kv_block=entry.bn)


@dataclasses.dataclass(frozen=True)
class AttnResolution:
    config: AttnConfig
    source: str                 # "cache" | "autotune" | "analytic"
    key: str


def attn_cache_key(arch: str, *, heads: int, kv_heads: int, head_dim: int,
                   kv_dtype_str: str, seq_len: int, hw: TpuTarget) -> str:
    """Key shape mirrors :func:`repro.tuning.cache.cache_key`: leading
    ``hw.name`` (fleet merging partitions by target), then the arch under
    an ``attn.`` namespace no GEMM dtype string can produce, the KV
    storage dtype (int8 pages tile differently from bf16 slabs), the head
    geometry, and the bucketed kv length."""
    return (f"{hw.name}/attn.{arch}/{kv_dtype_str}/"
            f"h{heads}kv{kv_heads}d{head_dim}/s{shape_bucket(seq_len)}")


# ---------------------------------------------------------------------------
# Analytic defaults
# ---------------------------------------------------------------------------

def _analytic_config(arch: str, *, heads: int, kv_heads: int, head_dim: int,
                     seq_len: int, kv_dtype, hw: TpuTarget) -> AttnConfig:
    """VMEM-heuristic defaults, the always-available floor.

    Paged: the page is the kv grid step, so it wants to be lane-width
    (128) for MXU efficiency but no larger than ~a quarter of the
    context (ragged tail waste and pool granularity).  Flash: grow kv
    then q blocks while the per-cell working set (q, k, v tiles + the
    (q_block, kv_block) score matrix, fp32, double-buffered streams)
    stays within an eighth of VMEM — the same occupancy fraction the
    GEMM solver targets for its double-buffers.
    """
    sb = shape_bucket(seq_len)
    if arch == "paged_decode":
        page = min(128, max(16, sb // 4))
        page = max(p for p in _PAGE_CANDIDATES if p <= page)
        return AttnConfig(q_block=1, kv_block=page)

    budget = hw.vmem_bytes // 8
    best = (min(_FLASH_Q), min(_FLASH_KV))
    for kv in _FLASH_KV:
        for qb in _FLASH_Q:
            if qb > sb and qb > min(_FLASH_Q):
                continue
            g = max(1, heads // kv_heads)
            foot = 4 * (qb * g * head_dim          # q tile (fp32 rows)
                        + 2 * 2 * kv * head_dim    # k+v tiles, dbl-buffered
                        + qb * g * kv              # score matrix
                        + qb * g * head_dim)       # accumulator
            if foot <= budget and (kv, qb) >= (best[1], best[0]):
                best = (qb, kv)
    return AttnConfig(q_block=best[0], kv_block=min(best[1], sb))


# ---------------------------------------------------------------------------
# Timing the real kernels
# ---------------------------------------------------------------------------

def _time_call(fn, *args, **kwargs) -> float:
    for _ in range(_TUNE_WARMUP):
        jax.block_until_ready(fn(*args, **kwargs))
    best = float("inf")
    for _ in range(_TUNE_ITERS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        best = min(best, time.perf_counter() - t0)
    return best


def _tune_paged(heads: int, kv_heads: int, head_dim: int, seq_len: int,
                interpret: bool) -> Tuple[AttnConfig, float, int]:
    """Time the real paged int8 kernel across page-size candidates on a
    synthetic pool shaped like the bucketed workload."""
    from repro.kernels.flash_attn import paged_flash_attention_tpu

    sb = max(shape_bucket(seq_len), min(_PAGE_CANDIDATES))
    rng = np.random.default_rng(0)
    B = 2
    q = jnp.asarray(rng.normal(size=(B, heads, head_dim)).astype(np.float32))
    best: Tuple[float, Optional[AttnConfig]] = (float("inf"), None)
    tried = 0
    for page in _PAGE_CANDIDATES:
        if page > sb:
            continue
        NP = sb // page
        P = B * NP
        kp = jnp.asarray(rng.integers(-127, 128, size=(P, kv_heads, page,
                                                       head_dim), dtype=np.int8))
        vp = jnp.asarray(rng.integers(-127, 128, size=(P, kv_heads, page,
                                                       head_dim), dtype=np.int8))
        sc = jnp.full((P,), 0.02, jnp.float32)
        tables = jnp.arange(P, dtype=jnp.int32).reshape(B, NP)
        lens = jnp.full((B,), sb, jnp.int32)
        fn = jax.jit(lambda q_, k_, v_: paged_flash_attention_tpu(
            q_, k_, v_, sc, sc, tables, lens, interpret=interpret))
        t = _time_call(fn, q, kp, vp)
        tried += 1
        if t < best[0]:
            best = (t, AttnConfig(q_block=1, kv_block=page))
    assert best[1] is not None
    return best[1], best[0], tried


def _tune_flash(heads: int, kv_heads: int, head_dim: int, seq_len: int,
                dtype, interpret: bool) -> Tuple[AttnConfig, float, int]:
    from repro.kernels.flash_attn import flash_attention_tpu

    sb = max(shape_bucket(seq_len), min(_FLASH_Q))
    rng = np.random.default_rng(0)
    B = 1
    mk = lambda h: jnp.asarray(
        rng.normal(size=(B, sb, h, head_dim)).astype(np.float32)).astype(dtype)
    q, k, v = mk(heads), mk(kv_heads), mk(kv_heads)
    pos = jnp.arange(sb, dtype=jnp.int32)[None, :]
    best: Tuple[float, Optional[AttnConfig]] = (float("inf"), None)
    tried = 0
    for qb in _FLASH_Q:
        for kvb in _FLASH_KV:
            if qb > sb or kvb > sb:
                continue
            fn = jax.jit(lambda q_, k_, v_, qb=qb, kvb=kvb:
                         flash_attention_tpu(q_, k_, v_, q_positions=pos,
                                             kv_positions=pos, causal=True,
                                             q_block=qb, kv_block=kvb,
                                             interpret=interpret))
            t = _time_call(fn, q, k, v)
            tried += 1
            if t < best[0]:
                best = (t, AttnConfig(q_block=qb, kv_block=kvb))
    if best[1] is None:  # seq bucket below every candidate: nothing to tune
        return AttnConfig(q_block=min(_FLASH_Q), kv_block=min(_FLASH_KV)), \
            0.0, 0
    return best[1], best[0], tried


# ---------------------------------------------------------------------------
# Resolution (the registry port)
# ---------------------------------------------------------------------------

def _attn_memo(registry) -> Dict[str, AttnResolution]:
    # Piggyback on the registry instance so set_registry(None) in tests
    # drops attention memos together with GEMM ones.
    return registry.__dict__.setdefault("_attn_mem", {})


def resolve_attention(arch: str, *, heads: int, kv_heads: int, head_dim: int,
                      seq_len: int, kv_dtype=jnp.bfloat16,
                      hw: Optional[TpuTarget] = None,
                      registry=None) -> AttnResolution:
    """Resolve attention blocking with the registry's precedence.

    1. cache (in-memory memo, then the persistent tuning-cache file);
    2. autotune when the registry has it enabled — times the *real*
       kernel variant and persists the winner;
    3. the analytic VMEM heuristic.
    """
    from repro.obs.metrics import get_metrics
    from repro.tuning.registry import get_registry

    registry = registry or get_registry()
    hw = hw or registry.hw
    kv_dtype_str = jnp.dtype(kv_dtype).name
    key = attn_cache_key(arch, heads=heads, kv_heads=kv_heads,
                         head_dim=head_dim, kv_dtype_str=kv_dtype_str,
                         seq_len=seq_len, hw=hw)
    memo = _attn_memo(registry)
    hit = memo.get(key)
    if hit is not None:
        registry.stats["cache"] += 1
        get_metrics().counter(
            "tuning.cache_hit_total",
            "Registry resolutions served from cache").labels(
                tier="memory").inc()
        return hit

    entry = registry.cache.get(key)
    if entry is not None and entry.order == _ORDER_TAG:
        res = AttnResolution(AttnConfig.from_entry(entry), "cache", key)
        memo[key] = res
        registry.stats["cache"] += 1
        get_metrics().counter(
            "tuning.cache_hit_total",
            "Registry resolutions served from cache").labels(
                tier="persistent").inc()
        return res

    if registry.autotune_enabled:
        interpret = _auto_interpret()
        if arch == "paged_decode":
            cfg, measured, tried = _tune_paged(heads, kv_heads, head_dim,
                                               seq_len, interpret)
        else:
            cfg, measured, tried = _tune_flash(heads, kv_heads, head_dim,
                                               seq_len, kv_dtype, interpret)
        if tried:
            registry.cache.put(key, cfg.to_entry(measured_s=measured,
                                                 n_tried=tried))
            res = AttnResolution(cfg, "autotune", key)
            memo[key] = res
            registry.stats["autotune"] += 1
            get_metrics().counter(
                "tuning.autotune_total",
                "Resolutions answered by a fresh autotune run").inc()
            return res

    cfg = _analytic_config(arch, heads=heads, kv_heads=kv_heads,
                           head_dim=head_dim, seq_len=seq_len,
                           kv_dtype=kv_dtype, hw=hw)
    res = AttnResolution(cfg, "analytic", key)
    memo[key] = res
    registry.stats["analytic"] += 1
    get_metrics().counter(
        "tuning.solver_fallback_total",
        "Resolutions answered by the analytic model").labels(
            tier="attn").inc()
    return res


def resolve_page_size(*, heads: int, kv_heads: int, head_dim: int,
                      seq_len: int, hw: Optional[TpuTarget] = None,
                      registry=None) -> AttnResolution:
    """The serve engine's pool-construction query: the ``paged_decode``
    resolution whose ``kv_block`` is the page size."""
    return resolve_attention("paged_decode", heads=heads, kv_heads=kv_heads,
                             head_dim=head_dim, seq_len=seq_len,
                             kv_dtype=jnp.int8, hw=hw, registry=registry)

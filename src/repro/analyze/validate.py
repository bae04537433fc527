"""The program verifier: static checks on resolved dispatch plans.

Each function returns a list of :class:`repro.analyze.diagnostics.
Diagnostic` — empty means the plan satisfies every hard constraint the
kernels assume.  The checks deliberately *mirror* the constructive
guarantees of ``tuning/space.py`` / ``kernels/ca_mmm.py``: the solver
and autotuner only emit feasible configs, but persisted cache entries,
hand-built tiles and schema drift can all smuggle an infeasible plan to
the dispatch funnel, where it would otherwise die as a Pallas lowering
error (or silently, under ``python -O``, as garbage).

Paper anchors: the VMEM capacity constraint is Eq. 9 (tile solve under
on-chip memory), the per-tile scale rules come from the drain-fused
dequant contract (docs/QUANT.md), ring divisibility from the Eq. 6 wire
volume derivation over ``tp * pods`` k-chunks (docs/DISTRIBUTED.md).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp

from repro.analyze.diagnostics import Diagnostic, error, warning
from repro.core.hardware import TARGETS, TpuTarget, V5E
from repro.core.io_model import (VMEM_BUDGET_FRACTION, TileConfig,
                                 tile_vmem_bytes)

_VALID_ORDERS = ("k_inner", "k_outer")
_ATTN_ORDER = "attn"

# Short dtype names used by composite cache keys (quant_dtype_str).
_SHORT_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "int8": 1}


def _target_by_name(name: str) -> Optional[TpuTarget]:
    """Resolve a cache key's leading field: the registry mints keys with
    ``hw.name`` ('tpu-v5e'); the short spelling ('v5e') is accepted too."""
    for hw in TARGETS.values():
        if name in (hw.name, hw.name.removeprefix("tpu-")):
            return hw
    return None


def _itemsize(dtype) -> int:
    """Itemsize of a jnp dtype or a (short or full) dtype name."""
    if isinstance(dtype, str):
        if dtype in _SHORT_ITEMSIZE:
            return _SHORT_ITEMSIZE[dtype]
        return jnp.dtype(dtype).itemsize
    return jnp.dtype(dtype).itemsize


def _is_int8(dtype) -> bool:
    if dtype is None:
        return False
    if isinstance(dtype, str):
        return dtype in ("int8", "int8w")
    return jnp.dtype(dtype) == jnp.dtype(jnp.int8)


# ---------------------------------------------------------------------------
# GEMM programs (TAG002 / VMEM001 / QNT003)
# ---------------------------------------------------------------------------

def planned_tile_bytes(tag: str, config: TileConfig, *,
                       dtype=jnp.bfloat16, dtype_b=None, dtype_a=None,
                       scale_block: int = 0) -> int:
    """The VMEM bytes a resolved plan claims (Eq. 9 left-hand side):
    double-buffered streams, accumulators, and the program's extra
    residents, at the kernel's effective ``bk``."""
    from repro.kernels.program import program_cost

    cost = program_cost(tag)
    itemsize_in = _itemsize(dtype)
    return tile_vmem_bytes(
        config.bm, config.bn, scale_block or config.bk, itemsize_in,
        acc_bytes=4,
        epilogue_mn_ops=cost.stream_mn,
        epilogue_bias=cost.has_bias,
        itemsize_b=_itemsize(dtype_b) if dtype_b is not None
        else itemsize_in,
        n_b=cost.n_b, n_out=cost.n_out,
        prologue_mk_ops=cost.prologue_mk,
        prologue_kn_ops=cost.prologue_kn,
        itemsize_a=_itemsize(dtype_a) if dtype_a is not None
        else itemsize_in)


def validate_program(tag: str,
                     config: Optional[TileConfig],
                     hw: TpuTarget = V5E,
                     *,
                     dtype=jnp.bfloat16,
                     dtype_b=None,
                     dtype_a=None,
                     semiring: str = "plus_times",
                     scale_block: int = 0,
                     act_block: int = 0,
                     vmem_fraction: float = VMEM_BUDGET_FRACTION
                     ) -> List[Diagnostic]:
    """Verify one resolved GEMM program against its hard constraints.

    ``tag`` is the full program tag (prologue/combiner grammar included)
    the dispatch resolved under; ``config`` the tile it plans to launch
    (``None`` skips the VMEM check — tag/dtype-chain legality only).
    ``scale_block`` is the weight's per-tile scale block (0 =
    per-channel), ``act_block`` the per-k-tile activation scale block —
    both pin/constrain ``bk`` on the kernel path.
    """
    from repro.kernels.program import program_from_tag, program_tag

    diags: List[Diagnostic] = []

    # -- TAG002: the tag must parse, and parse canonically -----------------
    try:
        spec = program_from_tag(tag)
    except ValueError as e:
        diags.append(error("TAG002",
                           f"program tag {tag!r} does not parse: {e}",
                           tag=tag))
        return diags  # nothing downstream is well-defined
    round_trip = program_tag(spec)
    if round_trip != tag:
        diags.append(error(
            "TAG002",
            f"program tag {tag!r} is not canonical (round-trips to "
            f"{round_trip!r}) — cache keys minted from it would never "
            "hit the canonical entry", tag=tag, canonical=round_trip))

    # -- QNT003: dtype-chain legality --------------------------------------
    b_int8 = _is_int8(dtype_b)
    a_int8 = _is_int8(dtype_a)
    dequants = tuple(b.dequant for b in spec.branches)
    if b_int8 and any(d == "none" for d in dequants):
        diags.append(error(
            "QNT003",
            "int8 B operand but a branch has no dequant drain stage — "
            "the accumulator would be served unscaled",
            tag=tag, dequants=dequants))
    if a_int8:
        if not b_int8:
            diags.append(error(
                "QNT003",
                "int8 A stream without an int8 B operand — the "
                "int8 x int8 -> int32 MXU path needs both sides "
                "quantized", tag=tag))
        if any(d != "ab" for d in dequants):
            diags.append(error(
                "QNT003",
                "int8 A stream requires the 'ab' dequant stage on every "
                "branch (both scales apply at the drain)",
                tag=tag, dequants=dequants))

    # -- QNT003: scale-block alignment -------------------------------------
    if scale_block:
        if scale_block % hw.lane != 0:
            diags.append(error(
                "QNT003",
                f"per-tile weight scale block {scale_block} is not a "
                f"multiple of the lane width {hw.lane} — a streamed "
                "(bk, bn) block would straddle two scale rows",
                scale_block=scale_block, lane=hw.lane))
        if act_block and act_block != scale_block:
            diags.append(error(
                "QNT003",
                f"per-k-tile activation scale block {act_block} != "
                f"weight scale block {scale_block} — the kernel applies "
                "one fused scale per k-step partial",
                act_block=act_block, scale_block=scale_block))
    elif act_block and act_block % hw.lane != 0:
        diags.append(error(
            "QNT003",
            f"activation scale block {act_block} is not a multiple of "
            f"the lane width {hw.lane}", act_block=act_block,
            lane=hw.lane))

    # -- VMEM001: Eq. 9 capacity -------------------------------------------
    if config is not None:
        # Per-tile scales pin the kernel's k-step to the scale block
        # (kernels/ca_mmm.py), so that is the bk the budget must hold.
        eff_bk = scale_block or config.bk
        budget = int(hw.vmem_bytes * vmem_fraction)
        need = planned_tile_bytes(tag, config, dtype=dtype,
                                  dtype_b=dtype_b, dtype_a=dtype_a,
                                  scale_block=scale_block)
        if need > budget:
            diags.append(error(
                "VMEM001",
                f"tile ({config.bm}, {config.bn}, {eff_bk}) claims "
                f"{need} B of VMEM > budget {budget} B "
                f"({vmem_fraction:.2f} x {hw.vmem_bytes} B on {hw.name})",
                bm=config.bm, bn=config.bn, bk=eff_bk, bytes=need,
                budget=budget, hw=hw.name, tag=tag))
        if semiring == "min_plus":
            # The tropical kernel materializes the fp32 (bm, bk, bn)
            # broadcast of a[i,k] + b[k,j] before the min-reduce.
            bcast = config.bm * eff_bk * config.bn * 4
            if bcast > budget:
                diags.append(error(
                    "VMEM001",
                    f"min_plus broadcast buffer bm*bk*bn*4 = {bcast} B "
                    f"exceeds the VMEM budget {budget} B",
                    bm=config.bm, bn=config.bn, bk=eff_bk,
                    bytes=bcast, budget=budget, semiring=semiring))
    return diags


# ---------------------------------------------------------------------------
# Attention / KV pages (KV005)
# ---------------------------------------------------------------------------

def validate_attn(cfg,
                  *,
                  arch: str = "flash",
                  hw: TpuTarget = V5E,
                  heads: Optional[int] = None,
                  kv_heads: Optional[int] = None,
                  pool_pages: Optional[int] = None,
                  batch: Optional[int] = None,
                  max_context: Optional[int] = None,
                  table_pages: Optional[int] = None) -> List[Diagnostic]:
    """Verify a resolved :class:`repro.tuning.attention.AttnConfig`.

    For ``arch="paged_decode"`` the ``kv_block`` *is* the pool's page
    size, so the optional pool arguments extend the check to admission
    arithmetic: ``batch`` sequences of ``max_context`` tokens must fit
    ``pool_pages`` pages and ``table_pages`` block-table slots.
    """
    diags: List[Diagnostic] = []
    q_block = int(getattr(cfg, "q_block", 0) or 0)
    kv_block = int(getattr(cfg, "kv_block", 0) or 0)
    if q_block < 1 or kv_block < 1:
        diags.append(error(
            "KV005", f"non-positive attention blocking q_block={q_block} "
            f"kv_block={kv_block}", q_block=q_block, kv_block=kv_block))
        return diags

    if heads is not None and kv_heads:
        if heads % kv_heads != 0:
            diags.append(error(
                "KV005",
                f"GQA heads {heads} not divisible by kv heads {kv_heads}",
                heads=heads, kv_heads=kv_heads))

    if arch == "paged_decode":
        from repro.tuning.attention import _PAGE_CANDIDATES  # leaf import

        page = kv_block
        if page not in _PAGE_CANDIDATES:
            diags.append(error(
                "KV005",
                f"page size {page} is outside the supported candidate "
                f"set {_PAGE_CANDIDATES} — the paged kernel streams one "
                "page per grid step and the pool granularity is tuned "
                "over exactly these", page=page,
                candidates=_PAGE_CANDIDATES))
        if pool_pages is not None and batch and max_context:
            need = batch * (-(-int(max_context) // page))
            if need > pool_pages:
                diags.append(error(
                    "KV005",
                    f"pool admission overflow: {batch} sequences x "
                    f"{max_context} tokens need {need} pages of size "
                    f"{page}, pool holds {pool_pages}",
                    pages_needed=need, pool_pages=pool_pages,
                    page=page, batch=batch, max_context=max_context))
        if table_pages is not None and max_context:
            if table_pages * page < int(max_context):
                diags.append(error(
                    "KV005",
                    f"block table covers {table_pages} x {page} = "
                    f"{table_pages * page} tokens < max context "
                    f"{max_context}", table_pages=table_pages,
                    page=page, max_context=max_context))
    else:
        if kv_block % hw.lane != 0:
            diags.append(error(
                "KV005",
                f"flash kv_block {kv_block} is not a multiple of the "
                f"lane width {hw.lane}", kv_block=kv_block, lane=hw.lane))
    return diags


def validate_paged_dispatch(*, q_shape: Sequence[int], page: int,
                            n_heads: int, kv_heads: int
                            ) -> List[Diagnostic]:
    """The ``paged_attention`` call-site checks (shape/geometry only —
    lengths are traced values the verifier never sees)."""
    diags: List[Diagnostic] = []
    q_shape = tuple(int(d) for d in q_shape)
    if len(q_shape) != 4 or q_shape[1] != 1:
        diags.append(error(
            "KV005",
            f"paged decode attention takes q of shape (B, 1, H, D), got "
            f"{q_shape}", q_shape=q_shape))
    if page < 1:
        diags.append(error("KV005", f"non-positive page size {page}",
                           page=page))
    if kv_heads and n_heads % kv_heads != 0:
        diags.append(error(
            "KV005",
            f"GQA heads {n_heads} not divisible by kv heads {kv_heads}",
            heads=n_heads, kv_heads=kv_heads))
    return diags


# ---------------------------------------------------------------------------
# Distributed schedules (DIST004)
# ---------------------------------------------------------------------------

def validate_dist(schedule: str,
                  mesh: Union[Tuple[int, int, int], Dict[str, int]],
                  shapes: Tuple[int, int, int],
                  *,
                  b_block: int = 0,
                  scale_rows: int = 0) -> List[Diagnostic]:
    """Verify a distributed GEMM's geometry before the shard_map traces.

    ``mesh`` is ``(dp, tp, pods)`` or a dict with those keys; ``shapes``
    the global ``(m, n, k)``.  ``b_block`` is the weight's per-tile
    scale block (its rows ride the ring in k-chunks, so it must divide
    the chunk); ``scale_rows`` the scale tensor's leading dim (2.5-D
    meshes additionally split it over pods).  ``m`` may be ragged — the
    dispatch pads it to a ``dp`` multiple, so it is *not* checked.
    """
    from repro.core.distributed import SCHEDULES, _RING_SCHEDULES

    diags: List[Diagnostic] = []
    if isinstance(mesh, dict):
        dp = int(mesh.get("dp", 1))
        tp = int(mesh.get("tp", 1))
        pods = int(mesh.get("pods", 1))
    else:
        dp, tp, pods = (int(x) for x in mesh)
    m, n, k = (int(x) for x in shapes)

    if schedule not in SCHEDULES + ("auto",):
        diags.append(error(
            "DIST004", f"unknown schedule {schedule!r} (valid: "
            f"{SCHEDULES + ('auto',)})", schedule=schedule))
        return diags
    if min(dp, tp, pods) < 1:
        diags.append(error(
            "DIST004", f"non-positive mesh axis dp={dp} tp={tp} "
            f"pods={pods}", dp=dp, tp=tp, pods=pods))
        return diags
    if n % tp != 0:
        diags.append(error(
            "DIST004", f"n={n} does not divide over tp={tp}",
            n=n, tp=tp, schedule=schedule))
    if k % (tp * pods) != 0:
        diags.append(error(
            "DIST004", f"k={k} does not divide over tp*pods={tp * pods}",
            k=k, tp=tp, pods=pods, schedule=schedule))
    elif b_block and (schedule in _RING_SCHEDULES or schedule == "auto"):
        kchunk = k // (tp * pods)
        if kchunk % b_block != 0:
            diags.append(error(
                "DIST004",
                f"per-tile scale block {b_block} does not divide the "
                f"ring k-chunk {kchunk} — a rotated chunk would carry a "
                "fractional scale row", b_block=b_block, kchunk=kchunk,
                schedule=schedule))
        if pods > 1 and scale_rows and scale_rows % pods != 0:
            diags.append(error(
                "DIST004",
                f"per-tile scale rows {scale_rows} do not split over "
                f"pods={pods}", scale_rows=scale_rows, pods=pods))
    return diags


# ---------------------------------------------------------------------------
# Persisted tuning-cache entries (the `cache lint` mode)
# ---------------------------------------------------------------------------

def validate_cache_entry(key: str, entry) -> List[Diagnostic]:
    """Verify one persisted :class:`repro.tuning.cache.CacheEntry`
    against the current schema and budgets.

    GEMM keys re-run the tag + VMEM checks under the key's own hardware
    target and (possibly composite) dtype; attention keys check the
    order marker and page-candidate membership.  Unknown targets are
    flagged as warnings (a fleet cache may carry sections this build
    doesn't know), structural damage as errors.
    """
    diags: List[Diagnostic] = []
    parts = key.split("/")
    is_attn = len(parts) >= 2 and parts[1].startswith("attn.")

    if int(entry.bm) < 1 or int(entry.bn) < 1 or int(entry.bk) < 1:
        diags.append(error(
            "VMEM001", f"non-positive tile ({entry.bm}, {entry.bn}, "
            f"{entry.bk}) in cache entry", key=key))
        return diags

    if is_attn:
        if len(parts) != 5:
            diags.append(error(
                "TAG002", f"malformed attention cache key {key!r}",
                key=key))
            return diags
        if entry.order != _ATTN_ORDER:
            diags.append(error(
                "TAG002", f"attention key with order={entry.order!r} "
                f"(want 'attn')", key=key, order=entry.order))
        arch = parts[1][len("attn."):]
        from repro.tuning.attention import AttnConfig

        cfg = AttnConfig(q_block=int(entry.bm), kv_block=int(entry.bn))
        hw = _target_by_name(parts[0]) or V5E
        diags.extend(validate_attn(cfg, arch=arch, hw=hw))
        return diags

    if len(parts) != 6:
        diags.append(error(
            "TAG002", f"malformed GEMM cache key {key!r} (want "
            "hw/dtype/semiring/tag/layout/shape)", key=key))
        return diags
    hw_name, dtype_str, semiring, tag, layout, _shape = parts
    hw = _target_by_name(hw_name)
    if hw is None:
        diags.append(warning(
            "VMEM001", f"unknown hardware target {hw_name!r} — VMEM "
            "budget not checked", key=key, hw=hw_name))
        hw = V5E
    if entry.order not in _VALID_ORDERS:
        diags.append(error(
            "TAG002", f"unknown loop order {entry.order!r}", key=key,
            order=entry.order))
    dtype_a = dtype_b = None
    dtype = dtype_str
    if "w_" in dtype_str:            # composite quant key: "int8w_bf16a"
        w_part, a_part = dtype_str.split("w_", 1)
        dtype_b = w_part
        dtype = a_part[:-1] if a_part.endswith("a") else a_part
        dtype_a = dtype if _is_int8(dtype) else None
    try:
        cfg = TileConfig(bm=int(entry.bm), bn=int(entry.bn),
                         bk=int(entry.bk), order=entry.order)
        diags.extend(validate_program(
            tag, cfg, hw, dtype=dtype, dtype_b=dtype_b, dtype_a=dtype_a,
            semiring=semiring))
    except (TypeError, ValueError) as e:
        diags.append(error(
            "TAG002", f"cache entry fails to validate structurally: {e}",
            key=key))
    if layout not in ("nn", "nt", "tn", "tt"):
        diags.append(error(
            "TAG002", f"unknown layout {layout!r}", key=key,
            layout=layout))
    return diags

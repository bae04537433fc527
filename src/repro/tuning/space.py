"""Candidate tile-config generation, pruned by the paper's analytic model.

The empirical tuner does not search blindly: the I/O model (Eqs. 5-9 in
:mod:`repro.core.io_model`) already ranks tile shapes by effective
intensity under the VMEM capacity constraint, so the search space here is
*the model's top-N*, not a grid sweep.  This is the calibration pattern of
the SUMMA/WSE work (csl-experiments): let the analytic model nominate, let
the stopwatch elect.

Every emitted candidate is hardware-legal by construction:

* ``bm % qm == 0`` and ``bn % qn == 0`` for the dtype's (sublane, lane)
  quantum (Eq. 8 analog) and ``bk % lane == 0``;
* ``tile_vmem_bytes(...) <= vmem_fraction * hw.vmem_bytes``;
* min-plus candidates additionally keep the kernel's O(bm*bk*bn) broadcast
  inside the budget (the tropical kernel materializes it in VMEM).

Variants: each surviving tile shape is optionally crossed with the grid
``order`` axis ("k_inner" — the paper's schedule — and "k_outer", the
ablation the model predicts to lose; the tuner verifies the prediction
instead of assuming it).
"""

from __future__ import annotations

from typing import Iterable, List, Sequence, Tuple

import jax.numpy as jnp

from repro.core.hardware import TpuTarget, V5E
from repro.core.io_model import (VMEM_BUDGET_FRACTION, TileConfig,
                                 effective_intensity,
                                 io_lower_bound_elements, io_volume_elements,
                                 round_up_to, solve_tile_config,
                                 tile_vmem_bytes, vmem_quantum)

DEFAULT_TOP_N = 8
DEFAULT_BK_CANDIDATES = (128, 256, 512, 1024, 2048)


def _geometric_multiples(quantum: int, cap: int) -> List[int]:
    """quantum * 2^i up to cap, always including cap rounded to quantum."""
    vals = []
    v = quantum
    while v <= cap:
        vals.append(v)
        v *= 2
    capped = max(quantum, (cap // quantum) * quantum)
    if capped not in vals:
        vals.append(capped)
    return vals


def _min_plus_vmem_ok(bm: int, bn: int, bk: int, budget: int) -> bool:
    # Tropical kernel broadcasts (bm, bk, bn) fp32 in VMEM (ca_mmm.py).
    return bm * bk * bn * 4 <= budget


def candidate_tile_configs(
    m: int,
    n: int,
    k: int,
    dtype_in=jnp.bfloat16,
    dtype_acc=jnp.float32,
    hw: TpuTarget = V5E,
    vmem_fraction: float = VMEM_BUDGET_FRACTION,
    top_n: int = DEFAULT_TOP_N,
    orders: Sequence[str] = ("k_inner",),
    semiring: str = "plus_times",
    max_block: int = 8192,
    bk_candidates: Iterable[int] = DEFAULT_BK_CANDIDATES,
    epilogue: str = "none",
    dtype_b=None,
    dtype_a=None,
) -> List[TileConfig]:
    """Model-pruned candidate list, best-first by effective intensity.

    Returns up to ``top_n`` tile shapes (each crossed with ``orders``), the
    analytic :func:`solve_tile_config` answer always among them, so the
    tuner can never do worse than the pure model by construction.

    ``epilogue`` (a full *program tag* — prologue/combiner grammar
    included) charges the program's extra VMEM residents against the same
    budget: one (bm, bn) tile per streamed gate/residual operand plus a
    bias row for a fused drain, a second B double-buffer **and** a second
    accumulator for dual-branch (GLU) programs, and an fp32 (bm, bk)
    stream buffer per dact-prologue operand — so every program variant's
    candidates are feasible by construction.

    ``dtype_b`` (mixed-precision GEMMs, e.g. int8 weights under bf16
    activations) shrinks the B stream buffers in the budget: a quantized
    kernel's feasible region is *wider* than the uniform-dtype one, and
    the candidates here exploit that instead of inheriting bf16 limits.
    ``dtype_a`` (the w8a8 path's int8 activation stream) does the same
    for the A double buffer; the accumulator stays 4 B/element (int32 is
    as wide as fp32), so only the stream terms shrink.
    """
    from repro.kernels.program import program_cost  # no cycle: leaf module

    cost = program_cost(epilogue)
    epi_mn, epi_bias = cost.stream_mn, cost.has_bias
    n_b, n_out = cost.n_b, cost.n_out
    pro_mk, pro_kn = cost.prologue_mk, cost.prologue_kn
    itemsize_in = jnp.dtype(dtype_in).itemsize
    itemsize_b = jnp.dtype(dtype_b).itemsize if dtype_b is not None \
        else itemsize_in
    itemsize_a = jnp.dtype(dtype_a).itemsize if dtype_a is not None \
        else itemsize_in
    acc_bytes = jnp.dtype(dtype_acc).itemsize
    budget = int(hw.vmem_bytes * vmem_fraction)
    qm, qn = vmem_quantum(dtype_in, hw)
    qk = hw.lane

    m_cap = min(round_up_to(m, qm), max_block)
    n_cap = min(round_up_to(n, qn), max_block)
    bk_cap = min(round_up_to(k, qk), max(bk_candidates))
    bks = sorted({min(bk_cap, round_up_to(c, qk)) for c in bk_candidates})

    seen: set = set()
    shapes: List[Tuple[float, Tuple[int, int, int]]] = []

    def consider(bm: int, bn: int, bk: int) -> None:
        if bm <= 0 or bn <= 0 or bk <= 0:
            return
        if bm % qm or bn % qn or bk % qk:
            return
        if bm > m_cap or bn > n_cap or bk > bk_cap:
            return
        if tile_vmem_bytes(bm, bn, bk, itemsize_in, acc_bytes,
                           epilogue_mn_ops=epi_mn,
                           epilogue_bias=epi_bias,
                           itemsize_b=itemsize_b,
                           itemsize_a=itemsize_a,
                           n_b=n_b, n_out=n_out,
                           prologue_mk_ops=pro_mk,
                           prologue_kn_ops=pro_kn) > budget:
            return
        if semiring == "min_plus" and not _min_plus_vmem_ok(bm, bn, bk,
                                                            budget):
            return
        key = (bm, bn, bk)
        if key in seen:
            return
        seen.add(key)
        shapes.append((effective_intensity(bm, bn, bk, itemsize_in), key))

    # Seed with the analytic solution (clamped bk to the candidate cap).
    solved = solve_tile_config(m, n, k, dtype_in=dtype_in,
                               dtype_acc=dtype_acc, hw=hw,
                               vmem_fraction=vmem_fraction,
                               max_block=max_block, dtype_b=dtype_b,
                               dtype_a=dtype_a)
    consider(solved.bm, solved.bn, solved.bk)

    for bk in bks:
        for bm in _geometric_multiples(qm, m_cap):
            # Largest bn the budget allows at this (bm, bk), then a short
            # geometric descent below it — the model says intensity falls
            # monotonically with bn at fixed bm, so deep descent is waste.
            fixed = 2 * bm * bk * (itemsize_a + 4 * pro_mk)
            # B-side prologue blocks ((bk, bn) fp32) scale with bn, so
            # they join the per-bn slope, not the fixed term.
            per_bn = 2 * bk * (n_b * itemsize_b + 4 * pro_kn) \
                + bm * (n_b * acc_bytes + n_out * itemsize_in) \
                + epi_mn * bm * itemsize_in + (itemsize_in if epi_bias else 0)
            bn_budget = (budget - fixed) // per_bn if budget > fixed else 0
            bn_top = min((int(bn_budget) // qn) * qn, n_cap)
            if semiring == "min_plus":
                # Start the descent inside the broadcast-feasible region.
                bn_mp = (budget // (4 * bm * bk) // qn) * qn
                bn_top = min(bn_top, bn_mp)
            bn = bn_top
            for _ in range(3):
                if bn < qn:
                    break
                consider(bm, bn, bk)
                bn = max((bn // 2 // qn) * qn, 0)

    shapes.sort(key=lambda t: (-t[0], t[1]))
    top = shapes[:max(1, top_n)]

    out: List[TileConfig] = []
    for inten, (bm, bn, bk) in top:
        for order in orders:
            vb = tile_vmem_bytes(bm, bn, bk, itemsize_in, acc_bytes,
                                 epilogue_mn_ops=epi_mn,
                                 epilogue_bias=epi_bias,
                                 itemsize_b=itemsize_b,
                                 itemsize_a=itemsize_a,
                                 n_b=n_b, n_out=n_out,
                                 prologue_mk_ops=pro_mk,
                                 prologue_kn_ops=pro_kn)
            out.append(TileConfig(
                bm=bm, bn=bn, bk=bk, order=order, vmem_bytes=vb,
                intensity=inten,
                q_elements=io_volume_elements(m, n, k, min(bm, m),
                                              min(bn, n)),
                q_lower_bound=io_lower_bound_elements(
                    m, n, k, budget // max(itemsize_in, acc_bytes)),
                utilization=vb / hw.vmem_bytes,
            ))
    return out

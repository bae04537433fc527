"""Communication-avoiding MMM Pallas kernel — the paper's hardware mapping
(Sec. 4) re-targeted from an FPGA PE chain to the TPU MXU + VMEM.

Schedule (identical to the paper's, per DESIGN.md §2):

* The output block ``C[i, j]`` of shape ``(bm, bn)`` is the **memory tile**:
  it stays resident in a VMEM accumulator for the whole ``k`` loop
  (output-stationary outer-product schedule, paper Fig. 2/Lst. 2).
* ``A`` column panels and ``B`` row panels are **streamed**; Pallas's
  pipelined ``BlockSpec`` fetches are the Feed A / Feed B double buffers
  of paper Sec. 4.1 (two in-flight blocks per operand).
* The result is written back **once**, at ``k == K-1`` — the paper's
  drain-phase separation (Sec. 4.4): no double-buffered output tile, so the
  full fast memory budget serves the accumulator (the sqrt(2) intensity
  win over Dou [13] / Kumar [23]).
* Grid order ``(i, j, k)`` with ``k`` innermost ("arbitrary" semantics) —
  on TPU the MXU pipelines fp accumulation natively, so the paper's
  integer-only k-inner variant (Sec. 4.2) is legal for all dtypes.

The kernel executes :class:`repro.kernels.program.GemmProgramSpec`
**programs** — the paper's independent streaming stages made explicit:

* an optional **prologue** (rms_norm row/gain scaling, or the activation
  backward ``g·act'(h)``) runs on the decorated operand's tile right at
  the fetch, so the producer's output never takes an HBM round trip;
* 1..2 **B branches**, each with its own VMEM accumulator and its own
  drain chain (dequant / bias) — a dual-branch program streams the A
  panel *once* for both contractions (the reuse the paper's whole model
  optimizes for);
* the **combiner** (``glu``) drains ``act(v_gate) · v_up`` as a single
  write-back; plain programs drain each branch separately.

Ragged shapes run **natively**: the grid is ceil-divided and edge tiles
are masked in-kernel (zero fill for ``plus_times``, ``+inf`` for
``min_plus``) — no padded operand copies in HBM.  The drain store is
predicated by Pallas's block bounds, so a ragged C tile still causes
exactly one (partial) write-back.

``transpose_a`` / ``transpose_b`` stream a transposed operand directly
(swapped ``index_map`` + in-tile contraction on the other axis), so the
backward GEMMs ``dC @ B^T`` and ``A^T @ dC`` never materialize ``.T`` in
HBM — the paper's Sec. 4.3 on-the-fly transpose, done at the BlockSpec.

Tile sizes (bm, bn, bk) come from the kernel-config registry
(:mod:`repro.tuning`), which wraps :func:`repro.core.io_model.solve_tile_config`,
the paper's Eq. 5–9 solved over VMEM capacity and (sublane, lane) quanta;
program tags key each variant distinctly.

The kernel also supports the **distance product** (min-plus semiring), the
paper's Sec. 5.2 flexibility example, via ``semiring="min_plus"``.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import io_model
from repro.kernels.epilogue import EpilogueSpec, act_fn
from repro.kernels.program import (GemmProgramSpec, NO_PROLOGUE,
                                   PrologueSpec, PLAIN,
                                   apply_dact_reference, program_cost)


def _acc_dtype(dtype) -> jnp.dtype:
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        return jnp.dtype(jnp.int32)
    return jnp.dtype(jnp.float32)


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def layout_tag(transpose_a: bool, transpose_b: bool) -> str:
    """Canonical operand-layout key: 'nn' | 'nt' | 'tn' | 'tt'."""
    return ("t" if transpose_a else "n") + ("t" if transpose_b else "n")


def _default_tiles(m: int, n: int, k: int, dtype, semiring: str,
                   bm: Optional[int], bn: Optional[int], bk: Optional[int],
                   program_tag: str = "none", layout: str = "nn",
                   dtype_b=None, dtype_a=None):
    """None-means-solver: unspecified tile dims come from the registry.

    Callers can no longer silently bypass the I/O model with a stale
    literal default — an explicit (bm, bn, bk) is an intentional override,
    anything else is planned (cache > autotune > analytic precedence).
    """
    from repro.core.io_model import round_up_to  # lazy: cycle-free anyway

    if not (bm is not None and bn is not None and bk is not None):
        from repro.tuning import get_registry  # lazy: tuning times this module

        tile = get_registry().resolve(m, n, k, dtype=dtype, semiring=semiring,
                                      epilogue=program_tag, layout=layout,
                                      dtype_b=dtype_b, dtype_a=dtype_a)
        bm = bm if bm is not None else tile.bm
        bn = bn if bn is not None else tile.bn
        bk = bk if bk is not None else tile.bk
    # Clamp to the (quantized) problem size: a block larger than the
    # rounded-up dim only wastes VMEM, never changes the result.
    return (min(bm, round_up_to(m, 8)),
            min(bn, round_up_to(n, 128)),
            min(bk, round_up_to(k, 128)))


def _program_kernel(*refs, spec: GemmProgramSpec, semiring: str,
                    kdim: int, bk: int, transpose_a: bool, transpose_b: bool,
                    save_preact: bool, sb_per_tile: bool,
                    sa_per_tile: bool = False):
    """One grid step of a GemmProgram: the prologue-decorated A tile is
    contracted against each branch's B tile into that branch's VMEM
    accumulator; the per-branch drain chains + combiner run fused at the
    last k step, right before the single write-back per output.

    Quantized operands (repro.quant) ride the same schedule: int8 tiles
    stream from HBM, the cast to the compute dtype happens in VMEM, and
    the dequant rescale is either a drain stage (per-channel weight /
    per-row activation scales) or a per-k-step multiply of the partial
    product (per-tile scales, ``sb_per_tile``/``sa_per_tile`` — applied
    on *every* dequant branch: different k-blocks carry different scales,
    so a drain-time rescale would be wrong for any branch) — in all
    cases zero extra slow-memory traffic."""
    nb = spec.n_b
    pro = spec.prologue
    pos = 0
    a_ref = refs[pos]; pos += 1
    b_refs = refs[pos:pos + nb]; pos += nb

    # Prologue operand refs (ride the decorated stream's index map).
    row_ref = gain_ref = pre_ref = None
    if pro.kind == "rms":
        row_ref, gain_ref = refs[pos], refs[pos + 1]
        pos += 2
    elif pro.kind == "dact":
        pre_ref = refs[pos]
        pos += 1

    # Per-branch drain operand refs, branch-major, in chain order:
    # [scale_a], [scale_b], bias, mul, residual.
    branch_refs = []
    for bspec in spec.branches:
        deq = bspec.dequant
        names = []
        if deq == "ab":
            names.append("scale_a")
        if deq != "none":
            names.append("scale_b")
        if bspec.has_bias:
            names.append("bias")
        if bspec.has_mul:
            names.append("mul")
        if bspec.has_residual:
            names.append("residual")
        branch_refs.append({nm: refs[pos + i] for i, nm in enumerate(names)})
        pos += len(names)

    n_pre = nb if save_preact else 0
    out_refs = refs[pos:pos + spec.n_out]
    pre_refs = refs[pos + spec.n_out:pos + spec.n_out + n_pre]
    acc_refs = refs[-nb:]

    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        for acc_ref in acc_refs:
            if semiring == "min_plus":
                acc_ref[...] = jnp.full_like(acc_ref, jnp.inf)
            else:
                acc_ref[...] = jnp.zeros_like(acc_ref)

    def mask_k(x, axis, fill):
        # Edge tile on the contraction dim: out-of-range lanes hold
        # whatever the block fetch padded with (garbage) — neutralize
        # them (0 for plus_times, +inf for min_plus).  Statically a
        # no-op when bk divides k.
        if kdim % bk == 0:
            return x
        idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis) + k * bk
        return jnp.where(idx < kdim, x, jnp.asarray(fill, x.dtype))

    if semiring == "min_plus":
        a = a_ref[...].astype(jnp.float32)
        b = b_refs[0][...].astype(jnp.float32)
        a = mask_k(a, 1, jnp.inf)
        b = mask_k(b, 0, jnp.inf)
        # Tropical semiring: (min, +). Small bk keeps the broadcast in VMEM.
        cand = jnp.min(a[:, :, None] + b[None, :, :], axis=1)
        acc_refs[0][...] = jnp.minimum(acc_refs[0][...], cand)
    else:
        acc_t = acc_refs[0].dtype
        a = a_ref[...]
        # Prologue: the producer folded into the decorated tile's fetch.
        # Runs before the k-edge mask so any garbage it touches on edge
        # lanes is neutralized below.
        if pro.kind == "rms":
            af = (a.astype(jnp.float32) * row_ref[...]
                  * gain_ref[...].astype(jnp.float32))
            a = af.astype(a_ref.dtype)
        elif pro.kind == "dact" and pro.operand == "a":
            a = apply_dact_reference(a, pre_ref[...], pro.activation)
        if acc_t == jnp.int32:
            a = a.astype(jnp.int32)
        a = mask_k(a, 0 if transpose_a else 1, 0)
        # Contract the k axis of each *stored* tile — a transposed
        # operand is consumed in its HBM layout (no .T materialization).
        dims = (((0,) if transpose_a else (1,),
                 (1,) if transpose_b else (0,)), ((), ()))
        for i, acc_ref in enumerate(acc_refs):
            bspec = spec.branches[i]
            b = b_refs[i][...]
            if pro.kind == "dact" and pro.operand == "b":
                b = apply_dact_reference(b, pre_ref[...], pro.activation)
            if acc_t == jnp.int32:
                b = b.astype(jnp.int32)
            elif b.dtype != a.dtype and jnp.issubdtype(b.dtype, jnp.integer):
                # Weight-only quantization: int8 B tiles streamed, cast to
                # the activation dtype in VMEM (int8 values are exact in
                # bf16) — the HBM bytes are the int8 bytes, the MXU sees
                # its native float pairing.
                b = b.astype(a.dtype)
            b = mask_k(b, 1 if transpose_b else 0, 0)
            # Both operands integer under a float accumulator (per-tile
            # w8a8): contract exactly in int32, rescale into fp32 below —
            # the MXU's int8 pairing, not a float proxy.
            both_int = (jnp.issubdtype(a.dtype, jnp.integer)
                        and jnp.issubdtype(b.dtype, jnp.integer))
            dot_t = jnp.int32 if (acc_t != jnp.int32 and both_int) else acc_t
            part = jax.lax.dot_general(a, b, dims,
                                       preferred_element_type=dot_t)
            # Per-tile scales: this k-block's scale row rescales the
            # partial product before accumulation — for *every* dequant
            # branch (different blocks, different scales; a drain-time
            # rescale would silently mis-scale any branch skipped here).
            if sb_per_tile and bspec.dequant != "none":
                part = part.astype(acc_t) \
                    * branch_refs[i]["scale_b"][...].astype(acc_t)
            if sa_per_tile and bspec.dequant == "ab":
                part = part.astype(acc_t) \
                    * branch_refs[i]["scale_a"][...].astype(acc_t)
            acc_ref[...] += part.astype(acc_t)

    @pl.when(k == nk - 1)
    def _drain():
        # Paper Sec. 4.4: the drain is a separate, sequential phase — the
        # write-backs below are all the output traffic this program ever
        # causes (Q's n_out·mn term).  The fused per-branch chains and
        # the combiner ride those mandatory writes: their elementwise
        # work runs on the VMEM accumulators, never on an HBM round trip.
        vals = []
        for i, bspec in enumerate(spec.branches):
            z = acc_refs[i][...]
            ops = branch_refs[i]
            if bspec.is_identity:
                # No fp32 round trip for identity branches (int32
                # accumulators would lose precision past 2^24).
                if save_preact:
                    pre_refs[i][...] = z.astype(pre_refs[i].dtype)
                vals.append(z)
                continue
            zf = z.astype(jnp.float32)
            # Dequant first: later stages (bias/act/gate/residual) want
            # real units.  Per-tile scales were already applied per
            # k-step (on every dequant branch) — only per-channel /
            # per-row scales drain here.
            if bspec.dequant != "none" and not sb_per_tile:
                zf = zf * ops["scale_b"][...].astype(jnp.float32)
            if bspec.dequant == "ab" and not sa_per_tile:
                zf = zf * ops["scale_a"][...].astype(jnp.float32)
            if bspec.has_bias:
                zf = zf + ops["bias"][...].astype(jnp.float32)
            if save_preact:
                pre_refs[i][...] = zf.astype(pre_refs[i].dtype)
            zf = act_fn(bspec.activation)(zf)
            if bspec.has_mul:
                zf = zf * ops["mul"][...].astype(jnp.float32)
            if bspec.has_residual:
                zf = zf + ops["residual"][...].astype(jnp.float32)
            vals.append(zf)
        if spec.combine == "glu":
            y = act_fn(spec.combine_activation)(
                vals[0].astype(jnp.float32)) * vals[1].astype(jnp.float32)
            out_refs[0][...] = y.astype(out_refs[0].dtype)
        else:
            for i, v in enumerate(vals):
                out_refs[i][...] = v.astype(out_refs[i].dtype)


def ca_gemm_program(
    a: jax.Array,
    bs: Sequence[jax.Array],
    *,
    spec: GemmProgramSpec = PLAIN,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    out_dtype=None,
    semiring: str = "plus_times",
    interpret: bool = False,
    transpose_a: bool = False,
    transpose_b: bool = False,
    save_preact: bool = False,
    row_scale: Optional[jax.Array] = None,
    gain: Optional[jax.Array] = None,
    preact: Optional[jax.Array] = None,
    branch_operands: Optional[Sequence[Dict[str, jax.Array]]] = None,
    scale_b_block: int = 0,
    scale_a_block: int = 0,
):
    """Execute a :class:`GemmProgramSpec` with the paper's I/O-minimal
    schedule, for arbitrary (non-tile-multiple) shapes.

    ``a`` is the one streamed A operand; ``bs`` the 1..2 B operands (one
    accumulator each, same shape/dtype).  Prologue operands: ``row_scale``
    ((m, 1) fp32) + ``gain`` ((k,)) for the rms prologue; ``preact`` (the
    saved pre-activation, shaped like the decorated operand) for dact.
    ``branch_operands[i]`` carries branch ``i``'s drain operands
    (``bias``/``mul``/``residual``/``scale_a``/``scale_b``).

    Tile dims default to the kernel-config registry's plan under the
    program's tag (None-means-solver).  With ``save_preact`` each branch
    additionally drains its fp32 pre-combine value (``z`` after
    dequant + bias) and the call returns ``(*outputs, *preacts)`` — the
    saved tensors the trainable VJPs differentiate against.

    A quantized branch (``dequant != "none"``) streams int8 tiles and
    rescales inside the kernel: ``scale_b`` is the weight's per-channel
    column scale ((n,) fp32) or — with ``scale_b_block=g`` — per-tile
    scales of shape (ceil(k/g), n), in which case the kernel's k-tile is
    pinned to ``g`` so each streamed block sees exactly one scale row
    (applied to every dequant branch's k-step partial product —
    multi-branch programs included).  ``scale_a`` is the activation's
    scale for the full int8xint8 path ("ab"): per-row ((m,) fp32,
    applied at the drain) or — with ``scale_a_block=g`` — per-k-tile
    ((ceil(k/g),) fp32, applied per k-step like per-tile weight scales;
    when both operands are per-tile the blocks must agree).  Dequant
    adds no output traffic: it rides the drain (or the VMEM partial
    product), never an HBM round trip.
    """
    bs = tuple(bs)
    nb = len(bs)
    assert nb == spec.n_b, (nb, spec)
    branch_operands = list(branch_operands or [{} for _ in bs])
    assert len(branch_operands) == nb
    pro = spec.prologue

    if transpose_a:
        kdim, m = a.shape
    else:
        m, kdim = a.shape
    if transpose_b:
        n, k2 = bs[0].shape
    else:
        k2, n = bs[0].shape
    assert kdim == k2, f"contraction mismatch {a.shape} @ {bs[0].shape}"
    for b in bs[1:]:
        assert b.shape == bs[0].shape and b.dtype == bs[0].dtype, \
            "multi-branch programs share one B shape/dtype"
    if nb > 1:
        assert not (transpose_a or transpose_b), \
            "multi-branch programs stream the plain 'nn' layout"
    if semiring == "min_plus":
        assert spec.is_plain and not (transpose_a or transpose_b
                                      or save_preact), \
            "min_plus supports plain (A, B) programs only"
    if pro.kind == "rms":
        assert not transpose_a, "rms prologue decorates the natural A layout"
        assert row_scale is not None and gain is not None
        assert row_scale.shape == (m, 1), (row_scale.shape, m)
        assert gain.shape == (kdim,), (gain.shape, kdim)
    elif pro.kind == "dact":
        assert preact is not None
        if pro.operand == "a":
            assert not transpose_a and preact.shape == (m, kdim), \
                (preact.shape, m, kdim)
        else:
            assert not transpose_b and preact.shape == (kdim, n), \
                (preact.shape, kdim, n)

    deqs = [b.dequant for b in spec.branches]
    per_tile = scale_b_block > 0
    per_tile_a = scale_a_block > 0
    for i, bspec in enumerate(spec.branches):
        ops = branch_operands[i]
        if bspec.dequant != "none":
            assert semiring == "plus_times" and not (transpose_a
                                                     or transpose_b), \
                "quantized streaming supports the plain 'nn' layout"
            assert ops.get("scale_b") is not None, \
                "dequant needs the weight scales"
            if bspec.dequant == "ab":
                sa = ops.get("scale_a")
                assert sa is not None, "'ab' dequant needs activation scales"
                if per_tile_a:
                    assert sa.size == _ceil(kdim, scale_a_block), \
                        (sa.shape, kdim, scale_a_block)
                else:
                    assert sa.size == m, (sa.shape, m)
            else:
                assert not per_tile_a, \
                    "per-tile activation scales need an 'ab' dequant branch"
        else:
            assert ops.get("scale_a") is None and ops.get("scale_b") is None
            assert not (per_tile or per_tile_a), \
                "per-tile scales need a dequant stage on every branch"
    if per_tile or per_tile_a:
        # Per-tile dequant rescales each k-step's partial product, so the
        # kernel k-tile must equal the quantization block (both operands'
        # blocks, when both are per-tile).
        if per_tile and per_tile_a:
            assert scale_b_block == scale_a_block, \
                (scale_b_block, scale_a_block)
        bk = scale_b_block or scale_a_block

    tag = spec.tag()
    layout = layout_tag(transpose_a, transpose_b)
    any_deq = any(d != "none" for d in deqs)
    a_is_int = jnp.issubdtype(a.dtype, jnp.integer)
    dtype_b = bs[0].dtype if (any_deq and bs[0].dtype != a.dtype) else None
    dtype_a = None
    if any_deq and a_is_int:
        # w8a8: both operands stream int8 — plan/cache under the
        # composite int8w_int8a key, not the plain-int8 one.
        dtype_a, dtype_b = a.dtype, bs[0].dtype
    bm, bn, bk = _default_tiles(m, n, kdim, a.dtype, semiring, bm, bn, bk,
                                program_tag=tag, layout=layout,
                                dtype_b=dtype_b, dtype_a=dtype_a)
    if per_tile or per_tile_a:
        bk = scale_b_block or scale_a_block  # registry must not unpin it
    if any_deq and (per_tile or per_tile_a or not a_is_int):
        # Weight-only dequant (fp activations) and per-tile rescale both
        # accumulate in fp32 (the partial product is float either way).
        acc_t = jnp.dtype(jnp.float32)
    else:
        acc_t = _acc_dtype(a.dtype) if semiring == "plus_times" \
            else jnp.dtype(jnp.float32)
    if any_deq:
        out_dtype = out_dtype or (jnp.float32 if a_is_int else a.dtype)
    elif spec.combine == "glu":
        out_dtype = out_dtype or (jnp.float32 if a_is_int else a.dtype)
    else:
        out_dtype = out_dtype or (acc_t if acc_t == jnp.int32 else a.dtype)
    if semiring == "min_plus":
        out_dtype = jnp.float32

    grid = (_ceil(m, bm), _ceil(n, bn), _ceil(kdim, bk))
    if per_tile:
        for i, bspec in enumerate(spec.branches):
            if bspec.dequant == "none":
                continue
            sb = branch_operands[i]["scale_b"]
            assert sb.shape == (_ceil(kdim, bk), n), \
                (i, sb.shape, _ceil(kdim, bk), n)

    if transpose_a:
        a_spec = pl.BlockSpec((bk, bm), lambda i, j, kk: (kk, i))
    else:
        a_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
    if transpose_b:
        b_spec = pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk))
    else:
        b_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
    in_specs = [a_spec] + [b_spec] * nb
    operands = [a, *bs]

    # Prologue operands ride the decorated stream's index map.
    if pro.kind == "rms":
        operands.append(row_scale.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)))
        operands.append(gain.reshape(1, kdim))
        in_specs.append(pl.BlockSpec((1, bk), lambda i, j, kk: (0, kk)))
    elif pro.kind == "dact":
        operands.append(preact.astype(jnp.float32))
        if pro.operand == "a":
            in_specs.append(pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)))
        else:
            in_specs.append(pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)))

    for i, bspec in enumerate(spec.branches):
        ops = branch_operands[i]
        if bspec.is_identity:
            continue
        if bspec.dequant == "ab":
            if per_tile_a:
                # One scalar a-scale per k-step — the (1, 1) block's
                # index follows kk, like the per-tile weight scale rows.
                operands.append(ops["scale_a"].reshape(-1, 1)
                                .astype(jnp.float32))
                in_specs.append(
                    pl.BlockSpec((1, 1), lambda i, j, kk: (kk, 0)))
            else:
                # Per-row activation scales: a (bm, 1) column rides each i.
                operands.append(
                    ops["scale_a"].reshape(m, 1).astype(jnp.float32))
                in_specs.append(
                    pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)))
        if bspec.dequant != "none":
            if per_tile:
                # One (1, bn) scale row per k-step — index follows kk.
                operands.append(ops["scale_b"].astype(jnp.float32))
                in_specs.append(
                    pl.BlockSpec((1, bn), lambda i, j, kk: (kk, j)))
            else:
                # Per-channel column scales: one row, fetched like a bias.
                operands.append(
                    ops["scale_b"].reshape(1, n).astype(jnp.float32))
                in_specs.append(
                    pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        if bspec.has_bias:
            bias = ops.get("bias")
            assert bias is not None and bias.shape == (n,), (bias, n)
            # (1, n) layout: a bias row block rides along each (i, j) tile.
            operands.append(bias.reshape(1, n))
            in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        for name in ("mul", "residual"):
            if getattr(bspec, "has_" + name):
                arr = ops.get(name)
                assert arr is not None and arr.shape == (m, n), (name, arr)
                # Streamed (m, n) epilogue operand: fetched once per
                # (i, j) tile (index_map ignores kk — Pallas keeps the
                # buffer across the k loop), consumed at the drain.
                operands.append(arr)
                in_specs.append(
                    pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))

    # Inside a shard_map the outputs vary over the mesh axes the operands
    # vary over (empty outside one).
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    out_shape = [jax.ShapeDtypeStruct((m, n), out_dtype, vma=vma)
                 for _ in range(spec.n_out)]
    out_specs = [pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
                 for _ in range(spec.n_out)]
    if save_preact:
        for _ in range(nb):
            out_shape.append(jax.ShapeDtypeStruct((m, n), jnp.float32,
                                                  vma=vma))
            out_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))

    # Scoped VMEM: the tile's planned bytes (the solver's Eq. 9 count, at
    # this call's operand dtypes) plus headroom — Mosaic's default limit
    # refuses most solved tiles.
    cost = program_cost(tag)
    mn_itemsize = max((jnp.dtype(ops[name].dtype).itemsize
                       for bspec, ops in zip(spec.branches, branch_operands)
                       for name in ("mul", "residual")
                       if getattr(bspec, "has_" + name)),
                      default=jnp.dtype(a.dtype).itemsize)
    planned = io_model.tile_vmem_bytes(
        bm, bn, bk, mn_itemsize, acc_bytes=jnp.dtype(acc_t).itemsize,
        itemsize_out=jnp.dtype(out_dtype).itemsize,
        epilogue_mn_ops=cost.stream_mn, epilogue_bias=cost.has_bias,
        itemsize_a=jnp.dtype(a.dtype).itemsize,
        itemsize_b=jnp.dtype(bs[0].dtype).itemsize, n_b=cost.n_b,
        n_out=cost.n_out, prologue_mk_ops=cost.prologue_mk,
        prologue_kn_ops=cost.prologue_kn)
    if save_preact:
        planned += nb * bm * bn * 4
    kernel = functools.partial(
        _program_kernel, spec=spec, semiring=semiring, kdim=kdim, bk=bk,
        transpose_a=transpose_a, transpose_b=transpose_b,
        save_preact=save_preact, sb_per_tile=per_tile,
        sa_per_tile=per_tile_a)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_t) for _ in range(nb)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=io_model.kernel_vmem_limit_bytes(
                planned, bm, bn)),
        interpret=interpret,
        name="ca_gemm_program",
    )(*operands)
    if len(out) == 1:
        return out[0]
    return tuple(out)


def ca_mmm(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    out_dtype=None,
    semiring: str = "plus_times",
    interpret: bool = False,
    transpose_a: bool = False,
    transpose_b: bool = False,
    epilogue: Optional[EpilogueSpec] = None,
    bias: Optional[jax.Array] = None,
    mul: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    save_preact: bool = False,
    scale_a: Optional[jax.Array] = None,
    scale_b: Optional[jax.Array] = None,
    scale_b_block: int = 0,
    scale_a_block: int = 0,
    prologue: Optional[PrologueSpec] = None,
    row_scale: Optional[jax.Array] = None,
    gain: Optional[jax.Array] = None,
    preact: Optional[jax.Array] = None,
):
    """C = op(A) @ op(B) (+ fused prologue/epilogue): the single-branch
    program, with the historical keyword surface.

    This is now a thin builder over :func:`ca_gemm_program` — the
    epilogue spec becomes the program's one branch, the optional
    ``prologue`` decorates the streamed operand's fetch.
    """
    branch = epilogue if epilogue is not None else EpilogueSpec()
    spec = GemmProgramSpec(prologue=prologue or NO_PROLOGUE,
                           branches=(branch,))
    ops: Dict[str, jax.Array] = {}
    for name, arr in (("bias", bias), ("mul", mul), ("residual", residual),
                      ("scale_a", scale_a), ("scale_b", scale_b)):
        if arr is not None:
            ops[name] = arr
    out = ca_gemm_program(
        a, (b,), spec=spec, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
        semiring=semiring, interpret=interpret, transpose_a=transpose_a,
        transpose_b=transpose_b, save_preact=save_preact,
        row_scale=row_scale, gain=gain, preact=preact,
        branch_operands=[ops], scale_b_block=scale_b_block,
        scale_a_block=scale_a_block)
    return out


def ca_mmm_k_outer(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Ablation variant: k outermost, C blocks revisited from HBM.

    This is the schedule the paper's model *rejects*: each k step re-reads
    and re-writes the C tile through slow memory, inflating Q from
    ``mn (1 + k(1/x+1/y))`` to ``mnk/bk · 2 + ...``.  Used by
    ``benchmarks/bench_intensity.py`` to demonstrate the model's prediction.
    Tile dims default to the registry plan, as in :func:`ca_mmm`.
    Tile-divisible shapes only (ablation; callers pad).
    """
    m, kdim = a.shape
    _, n = b.shape
    bm, bn, bk = _default_tiles(m, n, kdim, a.dtype, "plus_times", bm, bn, bk)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0
    acc_t = _acc_dtype(a.dtype)
    out_dtype = out_dtype or (acc_t if acc_t == jnp.int32 else a.dtype)

    def kernel(a_ref, b_ref, c_ref):
        k = pl.program_id(0)

        @pl.when(k == 0)
        def _():
            c_ref[...] = jnp.zeros_like(c_ref)

        c_ref[...] += jnp.dot(
            a_ref[...], b_ref[...], preferred_element_type=acc_t
        ).astype(c_ref.dtype)

    grid = (kdim // bk, m // bm, n // bn)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda kk, i, j: (i, kk)),
            pl.BlockSpec((bk, bn), lambda kk, i, j: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda kk, i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), acc_t),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel", "parallel"),
        ),
        interpret=interpret,
        name="ca_mmm_k_outer",
    )(a, b).astype(out_dtype)

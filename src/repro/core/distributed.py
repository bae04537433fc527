"""Communication-avoiding *distributed* GEMM — the paper's Sec. 4.1 chain
argument applied at cluster scale (DESIGN.md §2, tier 2; docs/DISTRIBUTED.md).

The paper collapses its 2-D PE grid into a 1-D chain so that only 3 buses
cross each chiplet boundary (constant fan-out, neighbor-only links).  The
TPU analog of a chiplet crossing is an ICI hop (and, across pods, a DCN
hop).  We provide four schedules over a ``jax.shard_map``:

* ``allgather`` — SUMMA-style: gather the rotating operand up front.  This
  is the "broadcast" topology the paper argues *against*; kept as the
  baseline ablation (and it is what GSPMD emits by default).
* ``ring``      — output-stationary C, A panels rotate neighbor-to-neighbor
  via ``ppermute`` while each step's partial product is computed: the
  direct analog of the paper's PE chain (Fig. 4→Fig. 5 collapse).  The
  rotation is **explicitly double-buffered**: step *s* issues the permute
  feeding step *s+1* (and keeps the one feeding *s+2* in flight) *before*
  its local GEMM consumes the current buffer, with an
  ``optimization_barrier`` tying the in-flight transfers to the step's
  accumulator so XLA's latency-hiding scheduler cannot serialize them.
  Exactly ``g-1`` hops — the final dead rotation of the naive loop is
  gone.
* ``ring_unpipelined`` — the naive compute-then-rotate ``fori_loop`` ring
  (``g`` hops including the dead final one, no buffering).  Kept as the
  measured ablation ``benchmarks/bench_dist.py`` gates against; never
  chosen by ``auto``.
* ``summa25d``  — 2.5-D C-replication over the ``pod`` axis (Solomonik-
  Demmel [29], which the paper builds on): the k loop is split across
  pods, each pod runs the pipelined ring on 1/c of k, and C is reduced
  over the slow pod links once — trading cheap intra-pod bytes for scarce
  inter-pod bytes, the same "maximize reuse in the fastest tier" objective
  as Eq. 5.

Every schedule's per-step local GEMM resolves its tile through
``repro.tuning`` keyed by the per-device *local* shape
``(m/dp, n/tp, k/g)`` and composite dtype (``dist_local_resolution``),
int8/w8a8 ``QTensor`` weights ride the ring with their per-tile scales
(and a per-tensor-scaled w8a8 activation rides as int8 payload, halving
the rotated bytes), and each dispatch is recorded in the ``repro.obs``
ledger with its planned comm bytes (the Eq. 6 analog below) and overlap
model time.

``choose_schedule`` is the Eq. 6 cost model re-derived per device — now
per *step*: a pipelined schedule costs
``fill + (g-1) · max(step_compute, step_comm) + drain`` rather than the
aggregate ``max(compute, comm)``, so it distinguishes the pipelined from
the unpipelined ring; the dry-run prints its decision per GEMM.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.hardware import TpuTarget, V5E
from repro.core.io_model import TileConfig, io_volume_bytes

SCHEDULES = ("allgather", "ring", "ring_unpipelined", "summa25d")
# Schedules built on the rotating-A chain (share geometry + divisibility).
_RING_SCHEDULES = ("ring", "ring_unpipelined", "summa25d")


def _dist_error(message: str):
    """A DIST004 geometry violation as the single typed dispatch error."""
    from repro.analyze.diagnostics import ProgramValidationError, error

    return ProgramValidationError([error("DIST004", message)])

def _shard_map(f, mesh, in_specs, out_specs, check=True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


# ---------------------------------------------------------------------------
# Cost model (per-device, per-step Eq. 6 analog)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DistributedCost:
    """Planned cost of one distributed GEMM dispatch.

    ``comm_bytes`` is the total per-device wire traffic (the quantity
    ``BENCH_dist.json`` gates and the ledger pins); the ``step_*`` fields
    carry the per-ring-step decomposition the pipelined ``time_s`` is
    built from.  ``reduce_s`` is a terminal non-overlappable reduction
    (summa25d's C psum over DCN).
    """

    schedule: str
    compute_s: float
    comm_bytes: float
    comm_s: float
    overlapped: bool
    steps: int = 1
    step_compute_s: float = 0.0
    step_comm_s: float = 0.0
    reduce_s: float = 0.0

    @property
    def time_s(self) -> float:
        if self.overlapped and self.steps > 1:
            # Pipelined chain: one fill step of compute, then g-1 steps
            # each bounded by the slower of (local GEMM, in-flight hop),
            # then any terminal reduction.  Compute-bound this collapses
            # to compute_s; comm-bound to compute_s/g + comm_s — in both
            # regimes <= the unpipelined compute_s + comm_s.
            return (self.step_compute_s
                    + (self.steps - 1) * max(self.step_compute_s,
                                             self.step_comm_s)
                    + self.reduce_s)
        if self.overlapped:
            return max(self.compute_s, self.comm_s) + self.reduce_s
        return self.compute_s + self.comm_s + self.reduce_s


def dist_local_shapes(schedule: str, m: int, n: int, k: int, dp: int,
                      tp: int, pods: int = 1) -> Tuple[int, int, int, int]:
    """Per-device local GEMM shape ``(mloc, nloc, kloc, steps)``.

    Ring schedules run ``steps = tp`` local GEMMs over ``k/(tp·pods)``
    chunks; allgather runs one local GEMM over the full ``k/pods``
    range.  Ceil-divided so non-divisible query shapes still key a
    resolution (dispatch itself pads/asserts exact divisibility).
    """
    mloc = -(-m // dp)
    nloc = max(1, -(-n // tp))
    if schedule in _RING_SCHEDULES:
        return mloc, nloc, max(1, -(-k // (tp * max(pods, 1)))), tp
    if schedule == "allgather":
        return mloc, nloc, max(1, -(-k // max(pods, 1))), 1
    raise ValueError(schedule)


def _step_compute_s(mloc: int, nloc: int, kloc: int, hw: TpuTarget, dtype,
                    tile: Optional[TileConfig], dtype_b, dtype_a) -> float:
    """Roofline seconds of one local GEMM step under the resolved tile.

    Without a tile this is the seed's peak-FLOPs assumption; with one it
    is the max of the MXU term (at the int8 rate iff both operands ride
    int8 — mirroring the ledger's compute-dtype rule) and the Eq. 6 HBM
    term at the per-operand itemsizes.
    """
    compute_dtype = dtype
    if (dtype_a is not None and jnp.dtype(dtype_a) == jnp.dtype(jnp.int8)
            and dtype_b is not None
            and jnp.dtype(dtype_b) == jnp.dtype(jnp.int8)):
        compute_dtype = jnp.int8
    flops = 2.0 * mloc * nloc * kloc
    peak = flops / hw.peak_flops(compute_dtype)
    if tile is None:
        return peak
    itemsize = jnp.dtype(dtype).itemsize
    ia = jnp.dtype(dtype_a).itemsize if dtype_a is not None else itemsize
    ib = jnp.dtype(dtype_b).itemsize if dtype_b is not None else itemsize
    hbm = io_volume_bytes(mloc, nloc, kloc,
                          min(tile.bm, mloc), min(tile.bn, nloc),
                          a_itemsize=ia, b_itemsize=ib, out_itemsize=4)
    return max(peak, hbm / hw.hbm_bandwidth)


def estimate_cost(
    schedule: str,
    m: int,
    n: int,
    k: int,
    itemsize: int,
    dp: int,
    tp: int,
    pods: int = 1,
    hw: TpuTarget = V5E,
    dtype=jnp.bfloat16,
    *,
    tile: Optional[TileConfig] = None,
    dtype_b=None,
    dtype_a=None,
) -> DistributedCost:
    """Planned per-device cost of one schedule (the Eq. 6 analog).

    ``itemsize`` is the wire itemsize of the rotating A panel (1 when a
    w8a8 activation rides the ring as int8 payload).  ``tile`` (plus the
    composite ``dtype_b``/``dtype_a``) sharpens the compute term from
    peak FLOPs to the registry-resolved local-step roofline — pass the
    config from :func:`dist_local_resolution`.
    """
    pods = max(pods, 1)
    mloc, nloc, kloc, steps = dist_local_shapes(
        "ring" if schedule in _RING_SCHEDULES else schedule,
        m, n, k, dp, tp, pods)
    step_c = _step_compute_s(mloc, nloc, kloc, hw, dtype, tile,
                             dtype_b, dtype_a)
    link_bw = hw.ici_bandwidth
    hop_bytes = float(mloc) * kloc * itemsize      # one rotating A chunk
    if schedule == "allgather":
        # Gather A panels over the tp ring: each device receives
        # (tp-1)/tp of the (m/dp, k/pods) panel, then one local GEMM.
        bytes_ = (m / dp) * (k / pods) * (1 - 1 / tp) * itemsize
        return DistributedCost("allgather", step_c, bytes_,
                               bytes_ / link_bw, overlapped=False)
    if schedule == "ring":
        # g-1 in-flight hops, each hidden behind a local step.
        bytes_ = hop_bytes * (steps - 1)
        return DistributedCost("ring", step_c * steps, bytes_,
                               bytes_ / link_bw, overlapped=True,
                               steps=steps, step_compute_s=step_c,
                               step_comm_s=hop_bytes / link_bw)
    if schedule == "ring_unpipelined":
        # The naive loop rotates after every step — g hops including the
        # final dead one, and nothing guarantees the scheduler hides any
        # of them: charged serialized.
        bytes_ = hop_bytes * steps
        return DistributedCost("ring_unpipelined", step_c * steps, bytes_,
                               bytes_ / link_bw, overlapped=False,
                               steps=steps, step_compute_s=step_c,
                               step_comm_s=hop_bytes / link_bw)
    if schedule == "summa25d":
        # k split over pods: each pod's pipelined ring moves 1/pods of
        # the intra-pod bytes; C is all-reduced over the pod (DCN) axis
        # once — the only non-overlappable term.
        intra = hop_bytes * (steps - 1)
        c_bytes = 2.0 * (m / dp) * (n / tp) * (1 - 1 / pods) * 4  # fp32 acc
        comm_s = intra / link_bw + c_bytes / hw.dcn_bandwidth
        return DistributedCost("summa25d", step_c * steps, intra + c_bytes,
                               comm_s, overlapped=True, steps=steps,
                               step_compute_s=step_c,
                               step_comm_s=hop_bytes / link_bw,
                               reduce_s=c_bytes / hw.dcn_bandwidth)
    raise ValueError(schedule)


def dist_local_resolution(schedule: str, m: int, n: int, k: int, *,
                          dp: int, tp: int, pods: int = 1,
                          dtype=jnp.bfloat16, hw: TpuTarget = V5E,
                          dtype_b=None, dtype_a=None):
    """Resolve the per-step local GEMM's tile through the tuning registry.

    The key is the per-device **local** shape from
    :func:`dist_local_shapes` — not the global problem — under the
    local step's program tag (``none`` dense, ``dqb`` for int8 weights
    riding the ring, ``dqab`` for the w8a8 int8-activation ride) and
    composite dtype.  Returns ``(resolution, tag, (mloc, nloc, kloc,
    steps))``; ``resolution.key`` is the exact cache key (pinned by
    ``tests/test_distributed.py``).
    """
    from repro.kernels.epilogue import with_dequant  # lazy: kernels chain
    from repro.tuning import get_registry            # lazy: imports kernels

    mloc, nloc, kloc, steps = dist_local_shapes(schedule, m, n, k,
                                                dp, tp, pods)
    tag = "none"
    if dtype_b is not None:
        tag = with_dequant("none", "ab" if dtype_a is not None else "b")
    res = get_registry().resolve_full(
        mloc, nloc, kloc, dtype=dtype, hw=hw, epilogue=tag, layout="nn",
        dtype_b=dtype_b, dtype_a=dtype_a)
    return res, tag, (mloc, nloc, kloc, steps)


def choose_schedule(m, n, k, itemsize, dp, tp, pods=1, hw: TpuTarget = V5E,
                    dtype=jnp.bfloat16, *, tile: Optional[TileConfig] = None,
                    dtype_b=None, dtype_a=None,
                    use_registry: bool = False) -> DistributedCost:
    """Cheapest schedule under the per-step pipelined cost model.

    ``use_registry=True`` resolves each candidate's local-step tile
    through the kernel-config registry first, so the compute term uses
    the measured/analytic plan instead of assuming peak FLOPs
    (``ring_unpipelined`` is strictly dominated and never a candidate).
    """
    cands = ["allgather", "ring"]
    if pods > 1:
        cands.append("summa25d")
    costs = []
    for s in cands:
        t = tile
        if t is None and use_registry:
            res, _tag, _shapes = dist_local_resolution(
                s, m, n, k, dp=dp, tp=tp, pods=pods, dtype=dtype, hw=hw,
                dtype_b=dtype_b, dtype_a=dtype_a)
            t = res.config
        costs.append(estimate_cost(s, m, n, k, itemsize, dp, tp, pods, hw,
                                   dtype, tile=t, dtype_b=dtype_b,
                                   dtype_a=dtype_a))
    return min(costs, key=lambda c: c.time_s)


# ---------------------------------------------------------------------------
# Schedules (shard_map implementations)
# ---------------------------------------------------------------------------

def _ring_chain(a_blk, acc0, partial_fn: Callable, *, axis: str, g: int,
                pipelined: bool = True, fault_stage: Optional[str] = None):
    """The rotating-A chain shared by every ring schedule.

    ``partial_fn(a_cur, chunk)`` computes one local partial product for
    the device-local chunk index ``chunk`` (a traced scalar); the chain
    owns rotation and accumulation.  Device j at step s holds A chunk
    ``(j - s) mod g`` — the paper's PE chain with 3 buses per hop.

    ``pipelined=True`` (the default) Python-unrolls the loop (g is the
    static tp degree) into an explicit double-buffered pipeline: the
    prologue permute puts step 1's chunk on the wire before step 0's
    GEMM starts, each step s issues the transfer feeding step s+2, and
    an ``optimization_barrier`` ties the step's accumulator to the
    in-flight buffers so neither the permute-start nor the dot can be
    reordered across the other — exactly ``g-1`` hops, no dead rotation.

    ``pipelined=False`` keeps the naive compute-then-rotate ``fori_loop``
    (g hops, the last one dead) as the measured ablation.
    """
    jdx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % g) for i in range(g)]

    if not pipelined:
        if fault_stage is not None:
            _dist_fault_check(fault_stage)   # fori_loop traces body once

        def step(s, carry):
            a_cur, acc = carry
            chunk = jnp.mod(jdx - s, g)
            acc = acc + partial_fn(a_cur, chunk)
            a_nxt = jax.lax.ppermute(a_cur, axis, perm)
            return (a_nxt, acc)

        _, acc = jax.lax.fori_loop(0, g, step, (a_blk, acc0))
        return acc

    acc = acc0
    a_cur = a_blk
    # Prologue: step 1's chunk goes on the wire before step 0 computes.
    a_nxt = jax.lax.ppermute(a_cur, axis, perm) if g > 1 else None
    for s in range(g):
        if fault_stage is not None:
            _dist_fault_check(fault_stage)   # one chaos index per step
        # Issue step s+2's transfer before consuming the current buffer.
        a_fut = (jax.lax.ppermute(a_nxt, axis, perm)
                 if s + 2 < g else None)
        chunk = jnp.mod(jdx - s, g)
        acc = acc + partial_fn(a_cur, chunk)
        pending = [buf for buf in (a_nxt, a_fut) if buf is not None]
        if pending:
            # Tie the in-flight transfers to this step's accumulator:
            # XLA's latency-hiding scheduler may move the permute
            # start/done around the dot but can no longer serialize the
            # transfer after the compute it is meant to hide behind.
            tied = jax.lax.optimization_barrier((acc, *pending))
            acc, pending = tied[0], list(tied[1:])
            a_nxt = pending[0]
            a_fut = pending[1] if len(pending) > 1 else None
        a_cur, a_nxt = a_nxt, a_fut
    return acc


def _dist_fault_check(stage: str) -> None:
    """Chaos hook (FaultPlan) on the distributed dispatch path — one
    positional GEMM-dispatch index per ring step."""
    from repro.core.gemm import _fault_check  # lazy: avoid import cycle

    _fault_check(stage)


def _dequant_rows(data_rows, scale_rows, block: int, dtype=jnp.float32):
    """Dequantize a k-slice of an int8 weight inside a shard_map body.

    ``scale_rows`` is the matching slice of the fp32 scale: ``(1, nloc)``
    per-channel (block=0) or ``(rows/block, nloc)`` per-tile.
    """
    s = scale_rows
    if block:
        s = jnp.repeat(scale_rows, block, axis=0)[:data_rows.shape[0]]
    return (data_rows.astype(jnp.float32) * s).astype(dtype)


def dist_matmul(
    a: jax.Array,
    b,
    mesh: Mesh,
    *,
    schedule: str = "auto",
    dp_axis: str = "data",
    tp_axis: str = "model",
    pod_axis: Optional[str] = None,
    out_dtype=None,
    hw: TpuTarget = V5E,
) -> jax.Array:
    """Distributed C = A @ B.

    Logical sharding: A is (m, k) sharded m over ``dp_axis`` and k over
    ``tp_axis``; B is (k, n) sharded n over ``tp_axis``; C comes back
    (m, n) sharded (dp, tp).  With ``pod_axis`` set (2.5-D), k is
    additionally split over pods and C partials are psum'd over the pod
    axis — A must then also be sharded k over (pod, tp).

    ``b`` may be a :class:`repro.quant.QTensor`: int8 weights ride the
    ring with their per-channel/per-tile scales (dequant folded into the
    per-step partial), and a weight carrying a per-tensor static
    ``act_scale`` quantizes A on entry so the int8 payload rides the ring
    at 1 B/element — the w8a8 serve path composed with tensor
    parallelism.  ``m`` may be ragged (padded to a ``dp`` multiple and
    sliced back); ``n`` and ``k`` must divide exactly.

    A failed dispatch (e.g. an injected ``FaultPlan`` kernel failure on a
    ring step) falls back to :func:`dist_matmul_reference` with the same
    operands/out_dtype when the GEMM fallback policy allows, counted in
    ``gemm.fallback_total{stage="dist_matmul"}``.
    """
    if schedule not in SCHEDULES + ("auto",):
        raise _dist_error(f"unknown schedule {schedule!r} "
                          f"(valid: {SCHEDULES + ('auto',)})")
    try:
        return _dist_matmul_impl(a, b, mesh, schedule=schedule,
                                 dp_axis=dp_axis, tp_axis=tp_axis,
                                 pod_axis=pod_axis, out_dtype=out_dtype,
                                 hw=hw)
    except Exception as e:  # chaos / kernel failure -> same-semantics oracle
        from repro.core.gemm import _note_fallback  # lazy: avoid cycle

        _note_fallback("dist_matmul", e)  # re-raises if fatal/disabled
        return dist_matmul_reference(a, b, mesh, dp_axis=dp_axis,
                                     tp_axis=tp_axis, pod_axis=pod_axis,
                                     out_dtype=out_dtype)


def _dist_matmul_impl(a, b, mesh, *, schedule, dp_axis, tp_axis, pod_axis,
                      out_dtype, hw):
    from repro.quant.scales import QTensor, quantize_activation

    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    dp = mesh.shape[dp_axis]
    tp = mesh.shape[tp_axis]
    pods = mesh.shape[pod_axis] if pod_axis else 1

    # -- quantized operand normalization ------------------------------------
    b_q = None
    if isinstance(b, QTensor):
        if b.fmt != "int8":
            b = b.dequantize(a.dtype)   # fp8 emulation: dense XLA path
        else:
            b_q = b
    a_is_int = jnp.issubdtype(a.dtype, jnp.integer)
    # Per-tensor static act scale -> A rides the ring as int8 payload
    # (1 B/element on the wire).  Per-k-tile act scales cannot factor out
    # of the rotated chunks, so they fake-quant on entry and ride float
    # (same grid/saturation as the single-host w8a8 oracle).
    ride_int8 = (b_q is not None and b_q.act_scale is not None
                 and b_q.act_block == 0 and not a_is_int)
    a_ride = a
    if b_q is not None and b_q.act_scale is not None and not a_is_int:
        if ride_int8:
            a_ride = quantize_activation(a, b_q.act_scale, 0)
        else:
            from repro.quant.scales import fake_quant_activation

            a_ride = fake_quant_activation(a, b_q.act_scale, b_q.act_block)
    dtype_b = jnp.int8 if b_q is not None else None
    dtype_a = jnp.int8 if ride_int8 else None
    b_block = b_q.block if b_q is not None else 0
    # Pure-int chain: every per-step partial is an int8xint8 -> int32 dot
    # (per-channel b scale and the scalar act scale both factor out of
    # the contraction and apply once at the drain).
    pure_int = (ride_int8 and b_block == 0) or (a_is_int and b_q is None)

    m_pad = -(-m // dp) * dp
    if m_pad != m:
        a_ride = jnp.pad(a_ride, ((0, m_pad - m), (0, 0)))

    # -- schedule choice + registry-tuned local step ------------------------
    if schedule == "auto":
        schedule = choose_schedule(
            m_pad, n, k, a_ride.dtype.itemsize, dp, tp, pods, hw, a.dtype,
            dtype_b=dtype_b, dtype_a=dtype_a, use_registry=True).schedule
    # -- geometry (DIST004): n over tp, k over tp*pods, per-tile scale
    # rows over the ring k-chunk — verified once per (schedule, mesh,
    # shape) and memoized; violations raise ProgramValidationError.
    from repro.analyze.preflight import preflight_dist  # lazy: analyze imports core

    preflight_dist(
        schedule, (dp, tp, pods), (m, n, k),
        b_block=b_block if schedule in _RING_SCHEDULES else 0,
        scale_rows=(int(b_q.scale.shape[0])
                    if (b_q is not None and b_block) else 0))
    res, tag, (mloc, nloc, kstep, steps) = dist_local_resolution(
        schedule, m_pad, n, k, dp=dp, tp=tp, pods=pods, dtype=a.dtype,
        hw=hw, dtype_b=dtype_b, dtype_a=dtype_a)
    tile = res.config
    cost = estimate_cost(schedule, m_pad, n, k, a_ride.dtype.itemsize,
                         dp, tp, pods, hw, a.dtype, tile=tile,
                         dtype_b=dtype_b, dtype_a=dtype_a)
    _record_dist(schedule=schedule, m=m_pad, n=n, k=k, dp=dp, tp=tp,
                 pods=pods, dtype=a.dtype, dtype_b=dtype_b, dtype_a=dtype_a,
                 tag=tag, cost=cost, tile=tile, source=res.source, hw=hw)

    acc_dtype = jnp.int32 if pure_int else jnp.float32
    from repro.core.gemm import dist_local_matmul, get_gemm_mode
    mode = get_gemm_mode()

    # -- operand plumbing ---------------------------------------------------
    kspec = (pod_axis, tp_axis) if pod_axis else tp_axis
    a_spec = P(dp_axis, kspec)
    out_specs = P(dp_axis, tp_axis)
    ring_b_spec = (P(pod_axis, tp_axis) if pod_axis else P(None, tp_axis))
    if b_q is not None:
        operands = (a_ride, b_q.data, b_q.scale)
        # per-channel (1, n) scales replicate over k; per-tile rows
        # follow b's k rows (split over pods on the 2.5-D meshes).
        scale_k = (pod_axis if (b_block and pod_axis
                                and schedule in _RING_SCHEDULES) else None)
        scale_spec = P(scale_k, tp_axis)
    else:
        operands = (a_ride, b)

    def local_partial(a_cur, b_rows, s_rows):
        """One chunk's partial product on this device."""
        if b_q is None:
            return dist_local_matmul(a_cur, b_rows, tile=tile, mode=mode,
                                     acc_dtype=acc_dtype)
        if pure_int:
            return jnp.dot(a_cur, b_rows, preferred_element_type=jnp.int32)
        bf = _dequant_rows(b_rows, s_rows, b_block)
        return jnp.dot(a_cur.astype(jnp.float32), bf,
                       preferred_element_type=jnp.float32)

    if schedule == "allgather":
        def f(a_loc, b_loc, s_loc=None):
            # Paper's rejected broadcast topology: full-panel gather.
            a_full = jax.lax.all_gather(a_loc, tp_axis, axis=1, tiled=True)
            if pod_axis:
                a_full = jax.lax.all_gather(a_full, pod_axis, axis=1,
                                            tiled=True)
            _dist_fault_check("dist_matmul")
            return local_partial(a_full, b_loc, s_loc)

        # b holds full k on every device (n-sharded only).  With a pod
        # axis the gathered result is value-replicated across pods but the
        # VMA system cannot prove it — disable the check for that case.
        in_specs = (a_spec, P(None, tp_axis)) + (
            (P(None, tp_axis),) if b_q is not None else ())
        c = _shard_map(f, mesh, in_specs, out_specs,
                       check=not pod_axis)(*operands)
    elif schedule in _RING_SCHEDULES:
        if schedule == "summa25d" and pod_axis is None:
            raise _dist_error("summa25d needs a replication (pod) axis")
        vary = (dp_axis, tp_axis) + ((pod_axis,) if pod_axis else ())

        def f(a_loc, b_loc, s_loc=None):
            kchunk = a_loc.shape[1]

            def partial_fn(a_cur, chunk):
                b_rows = jax.lax.dynamic_slice_in_dim(
                    b_loc, chunk * kchunk, kchunk, 0)
                s_rows = s_loc
                if s_loc is not None and b_block:
                    srows = kchunk // b_block
                    s_rows = jax.lax.dynamic_slice_in_dim(
                        s_loc, chunk * srows, srows, 0)
                return local_partial(a_cur, b_rows, s_rows)

            acc0 = jnp.zeros((a_loc.shape[0], b_loc.shape[1]), acc_dtype)
            if vary:
                # The zero carry starts device-invariant; mark it varying
                # over the manual axes so carry types match (VMA).
                acc0 = jax.lax.pcast(acc0, tuple(vary), to="varying")
            c_loc = _ring_chain(a_loc, acc0, partial_fn, axis=tp_axis,
                                g=tp,
                                pipelined=(schedule != "ring_unpipelined"),
                                fault_stage="dist_matmul")
            if pod_axis:
                c_loc = jax.lax.psum(c_loc, pod_axis)
            return c_loc

        in_specs = (P(dp_axis, (pod_axis, tp_axis)) if pod_axis else a_spec,
                    ring_b_spec) + (
            (scale_spec,) if b_q is not None else ())
        c = _shard_map(f, mesh, in_specs, out_specs)(*operands)
    else:
        raise ValueError(f"unknown schedule {schedule!r}")

    # -- drain: factored scales, output cast, ragged rows -------------------
    if ride_int8:
        scale = jnp.asarray(b_q.act_scale, jnp.float32).reshape(())
        c = c.astype(jnp.float32) * scale
        if b_block == 0:
            c = c * b_q.scale      # (1, n) column broadcast
    c = c.astype(out_dtype)
    if m_pad != m:
        c = c[:m]
    return c


def _record_dist(*, schedule, m, n, k, dp, tp, pods, dtype, dtype_b,
                 dtype_a, tag, cost, tile, source, hw):
    """Ledger hook: one `dist` record per dispatch (no-op when disabled)."""
    from repro.obs.ledger import get_ledger  # lazy: obs imports core

    led = get_ledger()
    if not led.enabled:
        return
    led.record_dist(
        schedule=schedule, m=m, n=n, k=k, dp=dp, tp=tp, pods=pods,
        dtype=dtype, dtype_b=dtype_b, dtype_a=dtype_a, tag=tag,
        steps=cost.steps,
        config={"bm": tile.bm, "bn": tile.bn, "bk": tile.bk,
                "order": tile.order, "mloc": int(-(-m // dp)),
                "nloc": int(n // tp), "kstep": int(k // (tp * pods))
                if schedule in _RING_SCHEDULES else int(k // pods)},
        config_source=source,
        planned_bytes=cost.comm_bytes,
        planned_flops=2.0 * m * n * k,
        planned_s=cost.time_s, hw=hw)


def dist_matmul_reference(a, b, mesh, dp_axis="data", tp_axis="model",
                          pod_axis=None, out_dtype=None):
    """Oracle: jit with sharding constraints only (GSPMD decides comms).

    Honors the same ``out_dtype`` contract as :func:`dist_matmul`
    (default: A's dtype) and the same QTensor semantics — per-tensor /
    per-tile static act scales fake-quant A on entry, the weight
    dequantizes through XLA — so parity tests compare like-for-like.
    """
    from repro.quant.scales import QTensor, fake_quant_activation

    out_dtype = out_dtype or a.dtype
    if isinstance(b, QTensor):
        if b.act_scale is not None and not jnp.issubdtype(a.dtype,
                                                          jnp.integer):
            a = fake_quant_activation(a, b.act_scale, b.act_block)
        b = b.dequantize(a.dtype)
    m = a.shape[0]
    m_pad = -(-m // mesh.shape[dp_axis]) * mesh.shape[dp_axis]
    if m_pad != m:   # same ragged-m contract as dist_matmul
        a = jnp.pad(a, ((0, m_pad - m), (0, 0)))
    s_a = NamedSharding(mesh, P(dp_axis, (pod_axis, tp_axis) if pod_axis
                                else tp_axis))
    s_b = NamedSharding(mesh, P(pod_axis, tp_axis) if pod_axis
                        else P(None, tp_axis))
    s_c = NamedSharding(mesh, P(dp_axis, tp_axis))

    acc = (jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer)
           else jnp.float32)

    def f(x, y):
        return jnp.dot(x, y, preferred_element_type=acc).astype(out_dtype)

    c = jax.jit(f, in_shardings=(s_a, s_b), out_shardings=s_c)(a, b)
    return c[:m] if m_pad != m else c

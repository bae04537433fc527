"""Empirical autotuner: time the model's top-N candidates, keep the winner.

The analytic model (Sec. 5.1) nominates candidates (:mod:`.space`), the
roofline (:func:`repro.core.io_model.gemm_roofline`) supplies a *prior* on
each candidate's runtime, and this module measures.  Measurement order is
best-prior-first so early stopping is sound:

* stop when the measured best is within ``early_stop_factor`` of the best
  roofline prior (nothing can beat the roofline by much — the remaining
  candidates have strictly worse priors), or
* stop after ``patience`` consecutive candidates without improvement.

On hosts without a TPU the kernel runs in Pallas interpret mode so tests
and CI can exercise the full tuning loop anywhere; the timings are then
only *relatively* meaningful, which is all the tuner needs.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hardware import TpuTarget, V5E
from repro.core.io_model import TileConfig, gemm_roofline
from repro.tuning import space as tspace

DEFAULT_WARMUP = 1
DEFAULT_ITERS = 3


def _auto_interpret() -> bool:
    """Pallas interpret mode unless a real TPU backend is attached."""
    return jax.default_backend() != "tpu"


def _pad_to_tiles(x: jax.Array, r0: int, r1: int) -> jax.Array:
    """Pad a 2D operand up to multiples of (r0, r1).

    Only the ``k_outer`` ablation needs this (its kernel keeps the
    divisibility requirement); the production schedule runs ragged
    shapes natively, so the padding lives here with its one consumer
    instead of in the kernels package.
    """
    p0 = -x.shape[0] % r0
    p1 = -x.shape[1] % r1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def _make_operands(m: int, n: int, k: int, dtype) -> Tuple[jax.Array,
                                                           jax.Array]:
    r = np.random.RandomState(0)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        a = jnp.asarray(r.randint(-4, 5, (m, k)), dtype)
        b = jnp.asarray(r.randint(-4, 5, (k, n)), dtype)
    else:
        a = jnp.asarray(r.randn(m, k), dtype)
        b = jnp.asarray(r.randn(k, n), dtype)
    return a, b


def time_tile(
    m: int,
    n: int,
    k: int,
    tile: TileConfig,
    dtype=jnp.bfloat16,
    semiring: str = "plus_times",
    interpret: Optional[bool] = None,
    warmup: int = DEFAULT_WARMUP,
    iters: int = DEFAULT_ITERS,
    epilogue: str = "none",
    layout: str = "nn",
    dtype_b=None,
    dtype_a=None,
) -> float:
    """Median wall seconds of one CA-MMM call under ``tile``.

    ``epilogue``/``layout`` time the kernel variant the config will
    actually serve — ``epilogue`` is a full *program tag*: synthetic
    bias/gate/residual operands are attached for fused drain stages,
    dual-branch (GLU) tags stream a second B operand into a second
    accumulator, prologue tags attach unit rms scales or a saved-preact
    stream, and 'nt'/'tn' layouts stream the transposed operand — so a
    cached entry holds a measurement of exactly the kernel variant its
    key names, never a proxy.  ``dtype_b`` (with a ``dq*`` stage) times
    the quantized-weight kernel: int8 B operand, unit per-channel scales
    — the streamed bytes and the drain-fused dequant are the real thing.
    ``dtype_a`` (with a ``dqab`` stage) additionally streams an int8 A
    operand with unit per-row a-scales — the full w8a8 variant, int32
    accumulation included.
    """
    from repro.kernels import ca_gemm_program, ca_mmm_k_outer, ops
    from repro.kernels.program import program_from_tag, synthetic_operands

    interpret = _auto_interpret() if interpret is None else interpret
    a, b = _make_operands(m, n, k, dtype)
    if dtype_b is not None and jnp.dtype(dtype_b) != jnp.dtype(dtype):
        _, b = _make_operands(m, n, k, dtype_b)
    if dtype_a is not None and jnp.dtype(dtype_a) != jnp.dtype(dtype):
        a, _ = _make_operands(m, n, k, dtype_a)

    if tile.order == "k_outer":
        if epilogue != "none" or layout != "nn":
            # The k_outer ablation kernel has no fused/transposed variant;
            # timing it as a proxy would cache a measurement of the wrong
            # kernel under a fused/transposed key.
            raise ValueError(
                f"k_outer cannot time epilogue={epilogue!r}/layout={layout!r}")
        from repro.core.io_model import round_up_to

        bm = min(tile.bm, round_up_to(m, 8))
        bn = min(tile.bn, round_up_to(n, 128))
        bk = min(tile.bk, round_up_to(k, 128))
        ap = _pad_to_tiles(a, bm, bk)
        bp = _pad_to_tiles(b, bk, bn)

        def call():
            return ca_mmm_k_outer(ap, bp, bm=bm, bn=bn, bk=bk,
                                  interpret=interpret)
    elif semiring != "plus_times":
        def call():
            return ops.ca_mmm_any(a, b, tile, interpret=interpret,
                                  semiring=semiring)
    else:
        # One branch covers every program tag x layout combination — the
        # executor treats them orthogonally, and the cache entry must
        # hold a measurement of exactly the variant its key names.
        prog = program_from_tag(epilogue)
        ta, tb = layout[0] == "t", layout[1] == "t"
        at = a.T if ta else a
        bt = b.T if tb else b
        pro_ops = synthetic_operands(epilogue, m, n, k, dtype)
        branch_ops = []
        for bspec in prog.branches:
            d = {}
            if bspec.has_bias:
                d["bias"] = jnp.ones((n,), a.dtype)
            if bspec.has_mul:
                d["mul"] = jnp.ones((m, n), a.dtype)
            if bspec.has_residual:
                d["residual"] = jnp.ones((m, n), a.dtype)
            if bspec.dequant != "none":
                d["scale_b"] = jnp.ones((n,), jnp.float32)
            if bspec.dequant == "ab":
                d["scale_a"] = jnp.ones((m,), jnp.float32)
            branch_ops.append(d)
        bs = (bt,) * prog.n_b

        def call():
            return ca_gemm_program(
                at, bs, spec=prog, bm=tile.bm, bn=tile.bn, bk=tile.bk,
                transpose_a=ta, transpose_b=tb, interpret=interpret,
                row_scale=pro_ops.get("row_scale"),
                gain=pro_ops.get("gain"), preact=pro_ops.get("preact"),
                branch_operands=branch_ops)

    for _ in range(max(0, warmup)):
        jax.block_until_ready(call())
    times = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


@dataclasses.dataclass(frozen=True)
class TuneResult:
    """Winner + provenance for one GEMM signature."""

    config: TileConfig
    measured_s: float
    predicted_s: float           # roofline prior of the winner
    n_tried: int
    trials: Tuple[Tuple[TileConfig, float], ...] = ()
    early_stopped: bool = False


def autotune_gemm(
    m: int,
    n: int,
    k: int,
    dtype=jnp.bfloat16,
    semiring: str = "plus_times",
    hw: TpuTarget = V5E,
    candidates: Optional[Sequence[TileConfig]] = None,
    max_candidates: int = tspace.DEFAULT_TOP_N,
    orders: Sequence[str] = ("k_inner",),
    patience: int = 3,
    early_stop_factor: float = 1.10,
    interpret: Optional[bool] = None,
    warmup: int = DEFAULT_WARMUP,
    iters: int = DEFAULT_ITERS,
    timer: Optional[Callable[[TileConfig], float]] = None,
    epilogue: str = "none",
    layout: str = "nn",
    dtype_b=None,
    dtype_a=None,
) -> TuneResult:
    """Measure model-nominated candidates; return the fastest.

    ``timer`` injects a measurement function (tests use a stub; production
    uses :func:`time_tile`).  Candidates are measured best-prior-first.
    ``epilogue``/``layout``/``dtype_b``/``dtype_a`` select the kernel
    variant being timed, so the winner cached under a fused/transposed/
    quantized key was measured as one.
    """
    if candidates is None:
        candidates = tspace.candidate_tile_configs(
            m, n, k, dtype_in=dtype, hw=hw, top_n=max_candidates,
            orders=orders, semiring=semiring, epilogue=epilogue,
            dtype_b=dtype_b, dtype_a=dtype_a)
    if epilogue != "none" or layout != "nn":
        # k_outer has no fused/transposed kernel variant — timing it as a
        # plain-GEMM proxy would let a wrong-variant measurement win the
        # fused/transposed cache key.
        candidates = [c for c in candidates if c.order != "k_outer"]
    if not candidates:
        raise ValueError(f"no legal tile candidates for {(m, n, k)}")

    if timer is None:
        def timer(tile: TileConfig) -> float:
            return time_tile(m, n, k, tile, dtype=dtype, semiring=semiring,
                             interpret=interpret, warmup=warmup, iters=iters,
                             epilogue=epilogue, layout=layout,
                             dtype_b=dtype_b, dtype_a=dtype_a)

    # Roofline prior orders the measurements; a k_outer schedule re-reads
    # the C tile per k step, which the prior reflects via inflated Q.
    def prior(tile: TileConfig) -> float:
        rl = gemm_roofline(m, n, k, tile, dtype, hw=hw)
        if tile.order == "k_outer":
            extra = (2.0 * m * n * (k // max(tile.bk, 1))
                     * jnp.dtype(dtype).itemsize) / hw.hbm_bandwidth
            return rl.time_s + extra
        return rl.time_s

    ranked = sorted(candidates, key=prior)
    best_prior = prior(ranked[0])

    from repro.obs import get_metrics, span

    trials: List[Tuple[TileConfig, float]] = []
    best: Optional[Tuple[TileConfig, float]] = None
    since_improved = 0
    early = False
    t_tune = time.perf_counter()
    with span("tune.gemm", m=m, n=n, k=k,
              dtype=jnp.dtype(dtype).name, epilogue=epilogue,
              layout=layout, candidates=len(ranked)):
        for tile in ranked:
            with span("tune.trial", bm=tile.bm, bn=tile.bn, bk=tile.bk,
                      order=tile.order):
                t = float(timer(tile))
            trials.append((tile, t))
            if best is None or t < best[1]:
                best = (tile, t)
                since_improved = 0
            else:
                since_improved += 1
            if best[1] <= early_stop_factor * best_prior:
                early = True
                break
            if since_improved >= patience:
                early = True
                break

    metrics = get_metrics()
    metrics.counter("tuning.autotune_trials_total",
                    "Candidate tiles measured by the autotuner").inc(
                        len(trials))
    metrics.histogram("tuning.autotune_seconds",
                      "Wall time of one autotune_gemm call").observe(
                          time.perf_counter() - t_tune)

    assert best is not None
    return TuneResult(config=best[0], measured_s=best[1],
                      predicted_s=float(prior(best[0])),
                      n_tried=len(trials), trials=tuple(trials),
                      early_stopped=early)

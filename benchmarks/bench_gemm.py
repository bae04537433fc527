"""Paper Table 2 analog: per-dtype CA-MMM kernels from the planner.

For each TPU-native dtype (bf16/fp32/int8 — the MXU-supported set standing
in for the paper's fp16/32/64+uints, DESIGN.md §8) this reports the solved
tile (x_tot, y_tot analog), arithmetic intensity (Op/Byte — the paper's
headline column), modeled Q, and projected performance at the v5e
roofline.  Wall-time is measured for the XLA path on this CPU host (the
kernel itself is validated in interpret mode by tests/test_kernels.py).

The **fused-epilogue** section runs a ragged decode shape (m=37 — a batch
of decode tokens, never a tile multiple) through the pad-free kernel and
compares the fused bias+activation drain against unfused GEMM + separate
epilogue: planned Q (the paper's Eq. 6 + epilogue traffic), XLA
``bytes accessed`` of the compiled computations, and a numerics check
against the jnp oracle.

The **quant** section (repro.quant) compares the int8-weight scaled-GEMM
plan against the bf16 plan on the same ragged decode shape: itemsize-
split planned bytes (the weight panel at 1 B/element), the drain-fused
dequant's scale-read-only overhead, and numerics vs both the
dequantized-weight oracle and the dense fp32 oracle.  ``--check-baseline``
gates the planned int8w/bf16 ratio at ``QUANT_RATIO_GATE``.

The **w8a8** section (static activation quantization) compares the full
int8xint8 plan against both bf16 and weight-only int8 on the same
decode shape: planned bytes with *both* panels at 1 B/element, the
roofline compute term at the MXU's 2x int8 rate (the compute-rate claim
this path exists for), and numerics of the quantize-on-entry kernel vs
the fake-quant oracle.  ``--check-baseline`` gates the w8a8/bf16 byte
ratio at ``W8A8_RATIO_GATE`` and the int8/bf16 compute ratio at 0.55.

The **glu** section compares the one-pass dual-branch SwiGLU program
(gate and up sharing the streamed x panel — two accumulators, one drain)
against the two-pass up + fused-gate formulation on a prefill FFN shape:
planned bytes from the shared-A extension of Eq. 6, XLA ``bytes
accessed`` of one jit vs two, numerics vs the oracle.
``--check-baseline`` gates the planned ratio at ``GLU_RATIO_GATE``.

``--tuned`` additionally runs the empirical autotuner (repro.tuning)
against the analytic plan on small shapes — in Pallas interpret mode on
CPU, on the real kernel on TPU — and reports the tuned-vs-analytic
speedup per shape.

Every run writes a machine-readable ``BENCH_gemm.json`` (stable schema,
see ``JSON_SCHEMA_VERSION``) with this run's records; the perf trajectory
across PRs lives in the file's git history, not in-file accumulation.
When a committed baseline exists, runs print per-record deltas against
it; ``--check-baseline`` turns a planned-bytes regression of the fused
path into a nonzero exit (the CI gate).
"""

import argparse
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (V5E, Epilogue, arithmetic_intensity_ops_per_byte,
                        epilogue_q_elements, gemm_roofline, io_volume_bytes,
                        io_volume_elements, solve_tile_config)
from repro.kernels.epilogue import stream_cost
from benchmarks.common import emit, time_call

N = 16384  # paper's benchmark size

# v2: adds per-record "kind" and the fused-epilogue section
# (planned_q_bytes_fused / _unfused, xla bytes accessed for both paths).
# v3: adds the "quant" section (int8-weight vs bf16 planned bytes on the
# ragged decode shape, drain-fused dequant numerics vs the fp32 oracle).
# v4: adds the "glu" section (one-pass dual-branch SwiGLU program vs the
# two-pass up + gate formulation: planned + XLA-measured bytes, ratio
# gated at <= GLU_RATIO_GATE).
# v5: adds the "w8a8" section (static-activation int8xint8 vs bf16 and
# int8w on the decode shape: planned bytes incl. the int8 A panel,
# roofline seconds at the MXU's 2x int8 rate, numerics vs the
# fake-quant oracle; byte ratio gated at <= W8A8_RATIO_GATE).
# v6: adds the top-level "model_error" section — per-entry measured_s /
# model_predicted_s ratio for every record that carries a wall
# measurement, plus geomean/min/max over the run.  This is the
# quantified model-vs-measured gap the ROADMAP "performance model v2"
# fit consumes (on this CPU container the ratios are orders of
# magnitude — that is the point: the error is now a tracked number,
# not an anecdote).
JSON_SCHEMA_VERSION = 6
DEFAULT_JSON_PATH = "BENCH_gemm.json"

# The ragged serving shape of the fused section: 37 decode tokens through
# a d=1024 projection (m is deliberately not a multiple of any sublane
# quantum; k, n are).
FUSED_SHAPE = (37, 1024, 1024)
FUSED_EPILOGUE = "bias+gelu"

# The quant section reuses the ragged decode shape (weight-panel traffic
# dominates at small m — the regime quantization halves) and gates the
# planned int8w/bf16 byte ratio at this ceiling in CI.
QUANT_RATIO_GATE = 0.6

# The w8a8 section reuses the decode shape: static activation scales
# put both panels at 1 B/element *and* the contraction on the MXU's 2x
# int8 rate — the first gate that is a compute-rate claim, not only a
# byte claim.  Planned w8a8/bf16 bytes gated at this ceiling in CI.
W8A8_RATIO_GATE = 0.6

# The GLU section runs a prefill FFN shape (rows x d_ff x d_model): the
# one-pass program's win is a whole A stream plus the up output's write
# and re-read — terms that matter when the x panel traffic is comparable
# to the weight panels' (at decode-m the two unavoidable weight streams
# dominate both formulations and the ratio tends to 1).
GLU_SHAPE = (512, 4096, 1024)
GLU_RATIO_GATE = 0.75
GLU_TAG = "glu.silu(none|none)"


def _record(m, n, k, dtype, tile, source, median_s, model_s, kind, **extra):
    """One stable-schema row for BENCH_gemm.json."""
    rec = {
        "kind": kind,                      # analytic | tuned | fused_epilogue
        "shape": [int(m), int(n), int(k)],
        "dtype": jnp.dtype(dtype).name,
        "config": {"bm": tile.bm, "bn": tile.bn, "bk": tile.bk,
                   "order": tile.order},
        "config_source": source,           # analytic | autotune | cache
        "median_s": float(median_s) if median_s is not None else None,
        "model_predicted_s": float(model_s),
    }
    rec.update(extra)
    return rec


def _baseline_index(baseline):
    if not baseline:
        return {}
    return {(r.get("kind", "analytic"), tuple(r["shape"]), r["dtype"]): r
            for r in baseline.get("results", [])}


def _delta_note(rec, base_idx, field):
    base = base_idx.get((rec["kind"], tuple(rec["shape"]), rec["dtype"]))
    if not base or base.get(field) is None or rec.get(field) is None:
        return "baseline=none"
    b, c = float(base[field]), float(rec[field])
    if b == 0:
        return "baseline=0"
    return f"baseline_{field}={b:.3g};delta={100.0 * (c - b) / b:+.1f}%"


def run(records=None):
    """Analytic section (Table 2 analog); appends rows to ``records``."""
    for dt, paper_ref in ((jnp.bfloat16, "fp16:956"), (jnp.float32, "fp32:302"),
                          (jnp.int8, "uint8:2073")):
        dt = jnp.dtype(dt)
        t = solve_tile_config(N, N, N, dtype_in=dt)
        ai = arithmetic_intensity_ops_per_byte(t.bm, t.bn, dt.itemsize)
        rl = gemm_roofline(N, N, N, t, dt)
        gops = 2.0 * N ** 3 / rl.time_s / 1e9
        q_gb = io_volume_elements(N, N, N, t.bm, t.bn) * dt.itemsize / 1e9
        # wall measurement on host (xla path, small size to stay sane on CPU)
        n_host = 1024
        a = jnp.ones((n_host, n_host), jnp.float32)
        f = jax.jit(lambda a, b: a @ b)
        us = time_call(f, a, a)
        emit(f"gemm_{dt.name}", us,
             f"tile={t.bm}x{t.bn}x{t.bk};AI={ai:.0f}Op/B(paper {paper_ref});"
             f"Q={q_gb:.1f}GB;proj={gops:.0f}GOp/s;bound={rl.bound};"
             f"vmem_util={t.utilization:.2f}")
        if records is not None:
            records.append(_record(
                N, N, N, dt, t, "analytic", None, rl.time_s, "analytic",
                ai_ops_per_byte=ai, q_gb=q_gb, projected_gops=gops,
                bound=rl.bound, vmem_utilization=t.utilization,
                host_xla_1024_us=us))


def _xla_bytes(compiled) -> float:
    ca = compiled.cost_analysis() or {}
    return float(ca.get("bytes accessed", 0.0))


def run_fused(records=None, shape=FUSED_SHAPE, dtypes=(jnp.float32,),
              base_idx=()):
    """Fused drain epilogue vs unfused GEMM + separate bias/activation.

    Planned Q is the model's verdict (deterministic — the CI gate); XLA
    ``bytes accessed`` of the compiled host computations corroborates it;
    the interpret-mode kernel run checks numerics against the oracle.
    """
    from repro.tuning import get_registry

    m, n, k = shape
    n_mn, has_bias = stream_cost(FUSED_EPILOGUE)
    r = np.random.RandomState(0)
    for dt in dtypes:
        dt = jnp.dtype(dt)
        resolution = get_registry().resolve_full(m, n, k, dtype=dt,
                                                 epilogue=FUSED_EPILOGUE)
        tile = resolution.config
        itemsize = dt.itemsize
        q_gemm = io_volume_elements(m, n, k, min(tile.bm, m),
                                    min(tile.bn, n))
        q_fused = (q_gemm + epilogue_q_elements(m, n, n_mn, has_bias,
                                                fused=True)) * itemsize
        q_unfused = (q_gemm + epilogue_q_elements(m, n, n_mn, has_bias,
                                                  fused=False)) * itemsize

        a = jnp.asarray(r.randn(m, k), dt)
        b = jnp.asarray(r.randn(k, n), dt)
        bias = jnp.asarray(r.randn(n), dt)

        # XLA view of the same fusion choice: one jit (epilogue fusable
        # into the GEMM consumer) vs two jits (the unfused z round trip
        # is forced through HBM).
        def fused_fn(a, b, bias):
            z = jnp.dot(a, b, preferred_element_type=jnp.float32)
            return jax.nn.gelu(z + bias).astype(dt)

        def gemm_fn(a, b):
            return jnp.dot(a, b, preferred_element_type=jnp.float32)

        def epi_fn(z, bias):
            return jax.nn.gelu(z + bias).astype(dt)

        fused_c = jax.jit(fused_fn).lower(a, b, bias).compile()
        gemm_c = jax.jit(gemm_fn).lower(a, b).compile()
        z_sds = jax.ShapeDtypeStruct((m, n), jnp.float32)
        epi_c = jax.jit(epi_fn).lower(z_sds, bias).compile()
        xla_fused = _xla_bytes(fused_c)
        xla_unfused = _xla_bytes(gemm_c) + _xla_bytes(epi_c)

        # Numerics: the pad-free fused kernel vs the oracle, on the
        # ragged shape (masked edge tiles + drain epilogue).
        from repro.kernels import fused_matmul

        got = fused_matmul(a, b, Epilogue(bias=bias, activation="gelu"),  # repro: noqa RPR001 -- kernel-vs-oracle check needs the raw kernel
                           tile, interpret=True)
        want = jax.nn.gelu(
            jnp.dot(a, b, preferred_element_type=jnp.float32)
            + bias.astype(jnp.float32)).astype(got.dtype)
        tol = 2e-2 if dt == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

        med = time_call(jax.jit(fused_fn), a, b, bias)
        rl = gemm_roofline(m, n, k, tile, dt)
        rec = _record(
            m, n, k, dt, tile, resolution.source, med * 1e-6, rl.time_s,
            "fused_epilogue",
            epilogue=FUSED_EPILOGUE,
            planned_q_bytes_fused=q_fused,
            planned_q_bytes_unfused=q_unfused,
            planned_q_saved_frac=1.0 - q_fused / q_unfused,
            xla_bytes_fused=xla_fused,
            xla_bytes_unfused=xla_unfused,
            numerics_ok=True)
        note = _delta_note(rec, base_idx, "planned_q_bytes_fused") \
            if base_idx else "baseline=none"
        emit(f"gemm_fused_{dt.name}_m{m}", med,
             f"epilogue={FUSED_EPILOGUE};tile={tile.bm}x{tile.bn}x{tile.bk};"
             f"plannedQ_fused={q_fused / 1e6:.3f}MB;"
             f"plannedQ_unfused={q_unfused / 1e6:.3f}MB;"
             f"saved={100 * rec['planned_q_saved_frac']:.1f}%;"
             f"xla_bytes_fused={xla_fused / 1e6:.3f}MB;"
             f"xla_bytes_unfused={xla_unfused / 1e6:.3f}MB;{note}")
        # A fused >= unfused regression is check_baseline's job to flag —
        # raising here would skip write_json and lose the very numbers
        # the CI artifact exists to preserve.
        if records is not None:
            records.append(rec)


def run_quant(records=None, shape=FUSED_SHAPE, base_idx=()):
    """int8-weight vs bf16 GEMM on the ragged decode shape (m=37).

    Planned streamed bytes come from the itemsize-split Eq. 6
    (``io_volume_bytes``): the weight panel moves 1 B/element instead of
    2, and at decode-m the weight term dominates, so the planned ratio
    lands near 0.5 — gated at <= 0.6 by ``--check-baseline``.  The
    dequant is drain-fused (an epilogue stage), so the quantized plan
    adds only the fp32 scale-row read — zero extra (m, n) round trips,
    which the planned-bytes identity below checks explicitly.
    """
    from repro.kernels import quant_matmul
    from repro.kernels.epilogue import with_dequant
    from repro.quant import quant_dtype_str, quantize
    from repro.tuning import get_registry

    m, n, k = shape
    act_dt = jnp.dtype(jnp.bfloat16)
    dtype_str = quant_dtype_str(act_dt, jnp.int8)
    r = np.random.RandomState(0)
    w32 = r.randn(k, n).astype(np.float32)
    a32 = r.randn(m, k).astype(np.float32)
    qw = quantize(jnp.asarray(w32), axis=-2)

    reg = get_registry()
    res_q = reg.resolve_full(m, n, k, dtype=act_dt, dtype_b=jnp.int8,
                             epilogue=with_dequant("none", "b"))
    res_bf = reg.resolve_full(m, n, k, dtype=act_dt)
    tq, tb = res_q.config, res_bf.config

    def planned(tile, b_is):
        return io_volume_bytes(m, n, k, min(tile.bm, m), min(tile.bn, n),
                               a_itemsize=2, b_itemsize=b_is,
                               out_itemsize=2)

    # Scale-row read: the dequant stage's entire extra traffic (fp32).
    scale_bytes = 4.0 * epilogue_q_elements(m, n, scale_b_elements=n)
    q_int8w = planned(tq, 1) + scale_bytes
    q_bf16 = planned(tb, 2)
    ratio = q_int8w / q_bf16

    # Numerics: drain-fused dequant kernel vs (a) its dequantized-weight
    # oracle (kernel correctness, tight) and (b) the dense fp32 oracle
    # (end-to-end accuracy incl. quantization error, the documented band).
    a_bf = jnp.asarray(a32, act_dt)
    got = np.asarray(quant_matmul(a_bf, qw, interpret=True), np.float32)  # repro: noqa RPR001 -- kernel-vs-oracle check needs the raw kernel
    oracle_deq = np.asarray(
        jnp.dot(a_bf, qw.dequantize(act_dt),
                preferred_element_type=jnp.float32), np.float32)
    oracle_f32 = a32 @ w32
    scale_ref = np.abs(oracle_f32).max()
    err_kernel = np.abs(got - oracle_deq).max() / scale_ref
    err_quant = np.abs(got - oracle_f32).max() / scale_ref
    assert err_kernel < 5e-3, err_kernel      # kernel == dequant oracle
    assert err_quant < 5e-2, err_quant        # int8 band (docs/QUANT.md)

    # Wall proxy matching the record's dtype story: bf16 activations
    # against the dequantized weight (XLA view of the quantized GEMM),
    # the convention the fused section follows with its dtype-matched fn.
    med = time_call(
        jax.jit(lambda a, w: jnp.dot(
            a, w, preferred_element_type=jnp.float32).astype(act_dt)),
        a_bf, qw.dequantize(act_dt))
    rl = gemm_roofline(m, n, k, tq, act_dt)
    rec = _record(m, n, k, act_dt, tq, res_q.source, med * 1e-6, rl.time_s,
                  "quant")
    rec["dtype"] = dtype_str  # composite key: int8 weights, bf16 acts
    rec.update(
        epilogue=with_dequant("none", "b"),
        planned_q_bytes_int8w=q_int8w,
        planned_q_bytes_bf16=q_bf16,
        planned_ratio=ratio,
        planned_q_saved_frac=1.0 - ratio,
        dequant_scale_bytes=scale_bytes,
        max_rel_err_vs_dequant_oracle=float(err_kernel),
        max_rel_err_vs_fp32_oracle=float(err_quant),
        numerics_ok=True)
    note = _delta_note(rec, base_idx, "planned_q_bytes_int8w") \
        if base_idx else "baseline=none"
    emit(f"gemm_quant_{dtype_str}_m{m}", med,
         f"tile={tq.bm}x{tq.bn}x{tq.bk};"
         f"plannedQ_int8w={q_int8w / 1e6:.3f}MB;"
         f"plannedQ_bf16={q_bf16 / 1e6:.3f}MB;ratio={ratio:.3f};"
         f"err_vs_fp32={err_quant:.2e};{note}")
    if records is not None:
        records.append(rec)


def run_w8a8(records=None, shape=FUSED_SHAPE, base_idx=()):
    """Static-activation int8xint8 vs bf16 and int8-weight-only.

    The w8a8 plan streams *both* panels at 1 B/element (planned bytes
    from the itemsize-split Eq. 6 with ``a_itemsize=1``) and runs the
    contraction at the MXU's 2x int8 rate (roofline seconds from
    ``peak_flops(int8)``) — the compute-rate claim on top of PR 3's byte
    claim.  Numerics: the interpret-mode kernel (quantize-on-entry with
    a calibrated static scale, int32 accumulation, drain dequant) vs the
    fake-quant XLA oracle (tight) and the dense fp32 oracle (the
    documented int8 band, now including activation quantization error).
    ``--check-baseline`` gates the planned w8a8/bf16 byte ratio at
    ``W8A8_RATIO_GATE`` and the int8/bf16 roofline compute ratio at 0.55.
    """
    from repro.kernels import quant_matmul
    from repro.quant import (Calibrator, QuantConfig, fake_quant_activation,
                             quant_dtype_str, quantize)
    from repro.tuning import get_registry

    m, n, k = shape
    act_dt = jnp.dtype(jnp.bfloat16)
    dtype_str = quant_dtype_str(jnp.int8, jnp.int8)
    r = np.random.RandomState(0)
    w32 = r.randn(k, n).astype(np.float32)
    a32 = r.randn(m, k).astype(np.float32)
    qw = quantize(jnp.asarray(w32), axis=-2)

    # Static a-scale from a one-batch calibration pass (absmax).
    cal = Calibrator(QuantConfig(act_fmt="int8"), axis=-1)
    cal.observe(jnp.asarray(a32))
    a_scale = cal.static_scale()

    reg = get_registry()
    res_w8a8 = reg.resolve_full(m, n, k, dtype=act_dt, dtype_b=jnp.int8,
                                dtype_a=jnp.int8, epilogue="dqab")
    res_w8 = reg.resolve_full(m, n, k, dtype=act_dt, dtype_b=jnp.int8,
                              epilogue="dqb")
    res_bf = reg.resolve_full(m, n, k, dtype=act_dt)
    t8a, t8, tb = res_w8a8.config, res_w8.config, res_bf.config

    def planned(tile, a_is, b_is):
        return io_volume_bytes(m, n, k, min(tile.bm, m), min(tile.bn, n),
                               a_itemsize=a_is, b_itemsize=b_is,
                               out_itemsize=2)

    # w8a8 extra traffic: the fp32 scale row (n) + the per-tensor
    # a-scale (1 element) — epilogue_q_elements' scale accounting.
    q_w8a8 = planned(t8a, 1, 1) \
        + 4.0 * epilogue_q_elements(m, n, scale_b_elements=n,
                                    scale_a_elements=1)
    q_w8 = planned(t8, 2, 1) \
        + 4.0 * epilogue_q_elements(m, n, scale_b_elements=n)
    q_bf16 = planned(tb, 2, 2)
    byte_ratio = q_w8a8 / q_bf16
    byte_ratio_vs_w8 = q_w8a8 / q_w8

    # Compute-rate side of the claim: the same 2mnk MACs at the MXU's
    # int8 rate vs the bf16 rate (deterministic hardware constants).
    flops = 2.0 * m * n * k
    compute_int8_s = flops / V5E.peak_flops(jnp.int8)
    compute_bf16_s = flops / V5E.peak_flops(act_dt)
    compute_ratio = compute_int8_s / compute_bf16_s

    # Numerics: quantize-on-entry kernel vs its fake-quant oracle and
    # the dense fp32 oracle (fp32 operands, so only quantization error).
    a_f = jnp.asarray(a32, jnp.float32)
    got = np.asarray(quant_matmul(a_f, qw, act_scale=a_scale,  # repro: noqa RPR001 -- kernel-vs-oracle check needs the raw kernel
                                  interpret=True), np.float32)
    oracle_fq = np.asarray(
        jnp.dot(fake_quant_activation(a_f, a_scale), qw.dequantize(),
                preferred_element_type=jnp.float32), np.float32)
    oracle_f32 = a32 @ w32
    scale_ref = np.abs(oracle_f32).max()
    err_kernel = np.abs(got - oracle_fq).max() / scale_ref
    err_quant = np.abs(got - oracle_f32).max() / scale_ref
    assert err_kernel < 5e-3, err_kernel   # kernel == fake-quant oracle
    assert err_quant < 1e-1, err_quant     # w8a8 band (docs/QUANT.md)

    # Wall proxy matching the record's dtype story (the XLA view of the
    # served math: fake-quant activations against the dequantized
    # weight), as the quant section does for w8.
    a_bf = jnp.asarray(a32, act_dt)
    med = time_call(
        jax.jit(lambda a, w: jnp.dot(
            a, w, preferred_element_type=jnp.float32).astype(act_dt)),
        fake_quant_activation(a_bf, a_scale), qw.dequantize(act_dt))
    model_s = max(compute_int8_s, q_w8a8 / V5E.hbm_bandwidth)
    rec = _record(m, n, k, act_dt, t8a, res_w8a8.source, med * 1e-6,
                  model_s, "w8a8")
    rec["dtype"] = dtype_str  # composite key: int8 weights, int8 acts
    rec.update(
        epilogue="dqab",
        planned_q_bytes_w8a8=q_w8a8,
        planned_q_bytes_int8w=q_w8,
        planned_q_bytes_bf16=q_bf16,
        planned_ratio=byte_ratio,
        planned_ratio_vs_int8w=byte_ratio_vs_w8,
        planned_q_saved_frac=1.0 - byte_ratio,
        compute_s_int8=compute_int8_s,
        compute_s_bf16=compute_bf16_s,
        compute_ratio=compute_ratio,
        max_rel_err_vs_fake_quant_oracle=float(err_kernel),
        max_rel_err_vs_fp32_oracle=float(err_quant),
        numerics_ok=True)
    note = _delta_note(rec, base_idx, "planned_q_bytes_w8a8") \
        if base_idx else "baseline=none"
    emit(f"gemm_w8a8_{dtype_str}_m{m}", med,
         f"tile={t8a.bm}x{t8a.bn}x{t8a.bk};"
         f"plannedQ_w8a8={q_w8a8 / 1e6:.3f}MB;"
         f"plannedQ_int8w={q_w8 / 1e6:.3f}MB;"
         f"plannedQ_bf16={q_bf16 / 1e6:.3f}MB;ratio={byte_ratio:.3f};"
         f"compute_ratio={compute_ratio:.2f};"
         f"err_vs_fp32={err_quant:.2e};{note}")
    if records is not None:
        records.append(rec)


def run_glu(records=None, shape=GLU_SHAPE, base_idx=()):
    """One-pass dual-branch SwiGLU program vs the two-pass formulation.

    Planned bytes come from the shared-A extension of Eq. 6
    (``io_volume_elements_program``: one A stream, two B streams, one
    drain) against ``two_pass_glu_q_elements`` (two full GEMMs plus the
    up output's write and mul-operand re-read).  XLA ``bytes accessed``
    of the compiled computations corroborates (one jit vs two jits —
    the two-pass u round trip is forced through memory); the
    interpret-mode kernel run checks numerics against the oracle.
    ``--check-baseline`` gates the planned one/two-pass ratio at
    ``GLU_RATIO_GATE``.
    """
    from repro.core.io_model import (io_volume_elements_program,
                                     two_pass_glu_q_elements)
    from repro.kernels import glu_matmul
    from repro.tuning import get_registry

    from repro.kernels.program import program_cost

    m, n, k = shape
    dt = jnp.dtype(jnp.float32)
    res = get_registry().resolve_full(m, n, k, dtype=dt, epilogue=GLU_TAG)
    tile = res.config
    # Planned Q straight from the program tag's cost shape, so an
    # rms-prologue GLU_TAG would automatically charge its vector reads.
    cost = program_cost(GLU_TAG)
    q_one = io_volume_elements_program(
        m, n, k, min(tile.bm, m), min(tile.bn, n),
        n_b=cost.n_b, n_out=cost.n_out,
        prologue_mk_ops=cost.prologue_mk,
        prologue_kn_ops=cost.prologue_kn,
        prologue_vec_elements=(m + k) if cost.prologue_vec else 0) \
        * dt.itemsize
    # The two-pass baseline's GEMMs plan under their own keys: the up
    # GEMM is a plain "none" kernel, the gate GEMM a fused "silu+mul"
    # one (whose streamed-mul VMEM resident can shrink its tile).
    t_up = get_registry().resolve(m, n, k, dtype=dt)
    t_gate = get_registry().resolve(m, n, k, dtype=dt, epilogue="silu+mul")
    q_two = two_pass_glu_q_elements(
        m, n, k, min(t_up.bm, m), min(t_up.bn, n),
        min(t_gate.bm, m), min(t_gate.bn, n)) * dt.itemsize
    ratio = q_one / q_two

    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(m, k), dt)
    wg = jnp.asarray(r.randn(k, n), dt)
    wu = jnp.asarray(r.randn(k, n), dt)

    def one_fn(x, wg, wu):
        g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
        return (jax.nn.silu(g) * u).astype(dt)

    def up_fn(x, wu):
        return jnp.dot(x, wu, preferred_element_type=jnp.float32).astype(dt)

    def gate_fn(x, wg, u):
        g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
        return (jax.nn.silu(g) * u).astype(dt)

    one_c = jax.jit(one_fn).lower(x, wg, wu).compile()
    up_c = jax.jit(up_fn).lower(x, wu).compile()
    u_sds = jax.ShapeDtypeStruct((m, n), dt)
    gate_c = jax.jit(gate_fn).lower(x, wg, u_sds).compile()
    xla_one = _xla_bytes(one_c)
    xla_two = _xla_bytes(up_c) + _xla_bytes(gate_c)

    # Numerics: the dual-branch program kernel vs the oracle.  Scale-
    # relative bound: the tiled k accumulation reorders fp32 adds, which
    # blows past a pointwise rtol exactly where silu crosses zero.
    got = np.asarray(glu_matmul(x, wg, wu, tile=tile, interpret=True),  # repro: noqa RPR001 -- kernel-vs-oracle check needs the raw kernel
                     np.float32)
    want = np.asarray(one_fn(x, wg, wu), np.float32)
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-5, err

    med = time_call(jax.jit(one_fn), x, wg, wu)
    rl = gemm_roofline(m, n, k, tile, dt)
    rec = _record(m, n, k, dt, tile, res.source, med * 1e-6, rl.time_s,
                  "glu",
                  epilogue=GLU_TAG,
                  planned_q_bytes_one_pass=q_one,
                  planned_q_bytes_two_pass=q_two,
                  planned_ratio=ratio,
                  planned_q_saved_frac=1.0 - ratio,
                  xla_bytes_one_pass=xla_one,
                  xla_bytes_two_pass=xla_two,
                  numerics_ok=True)
    note = _delta_note(rec, base_idx, "planned_q_bytes_one_pass") \
        if base_idx else "baseline=none"
    emit(f"gemm_glu_{dt.name}_m{m}", med,
         f"program={GLU_TAG};tile={tile.bm}x{tile.bn}x{tile.bk};"
         f"plannedQ_one={q_one / 1e6:.3f}MB;"
         f"plannedQ_two={q_two / 1e6:.3f}MB;ratio={ratio:.3f};"
         f"xla_bytes_one={xla_one / 1e6:.3f}MB;"
         f"xla_bytes_two={xla_two / 1e6:.3f}MB;{note}")
    if records is not None:
        records.append(rec)


def run_tuned(sizes=(128, 256), dtypes=(jnp.float32,), iters=2,
              max_candidates=4, records=None, base_idx=()):
    """Tuned-vs-analytic comparison (the ``--tuned`` mode).

    Interpret-mode timings on CPU are only *relatively* meaningful — which
    is exactly what a tuned/analytic ratio needs.
    """
    from repro.tuning import get_registry
    from repro.tuning.autotune import time_tile

    # Tune *through* the registry so winners land in the persistent cache
    # (and a second bench run reports config_source=cache, not autotune).
    registry = get_registry()
    registry.autotune_enabled = True
    for size in sizes:
        m = n = k = size
        for dt in dtypes:
            dt = jnp.dtype(dt)
            analytic = solve_tile_config(m, n, k, dtype_in=dt)
            analytic_s = time_tile(m, n, k, analytic, dtype=dt,
                                   warmup=1, iters=iters)
            res = registry.resolve_full(m, n, k, dtype=dt, iters=iters,
                                        max_candidates=max_candidates)
            entry = registry.cache.get(res.key)
            # Re-time the winner under identical conditions for a fair
            # tuned/analytic ratio (cached measured_s may be stale).
            tuned_s = time_tile(m, n, k, res.config, dtype=dt,
                                warmup=1, iters=iters)
            speedup = analytic_s / tuned_s
            rl = gemm_roofline(m, n, k, res.config, dt)
            rec = _record(
                m, n, k, dt, res.config, res.source,
                tuned_s, rl.time_s, "tuned",
                analytic_config={"bm": analytic.bm, "bn": analytic.bn,
                                 "bk": analytic.bk,
                                 "order": analytic.order},
                analytic_median_s=float(analytic_s),
                tuned_vs_analytic_speedup=float(speedup),
                candidates_tried=entry.n_tried if entry else 0)
            note = _delta_note(rec, base_idx, "median_s") if base_idx \
                else "baseline=none"
            emit(f"gemm_tuned_{dt.name}_{size}", tuned_s * 1e6,
                 f"tuned={res.config.bm}x{res.config.bn}x{res.config.bk};"
                 f"analytic={analytic.bm}x{analytic.bn}x{analytic.bk};"
                 f"analytic_us={analytic_s * 1e6:.1f};"
                 f"speedup={speedup:.2f}x;"
                 f"tried={entry.n_tried if entry else 0};"
                 f"registry_source={res.source};{note}")
            if records is not None:
                records.append(rec)


def check_baseline(records, base_idx) -> int:
    """CI gate: fail if the fused path regresses planned bytes vs the
    committed baseline (or stops beating the unfused path).

    ``base_idx`` is the already-parsed index from ``_baseline_index``
    (empty when no baseline file was readable — the fused-vs-unfused
    invariant is still enforced)."""
    failures = 0
    for rec in records:
        if rec["kind"] == "glu":
            # The dual-branch program's whole point is the shared-A byte
            # win: the planned one/two-pass ratio must clear the gate and
            # never regress vs the committed baseline.
            if rec["planned_ratio"] > GLU_RATIO_GATE:
                print(f"REGRESSION {rec['shape']}/{rec['dtype']}: planned "
                      f"one/two-pass GLU ratio {rec['planned_ratio']:.3f} > "
                      f"{GLU_RATIO_GATE}")
                failures += 1
            base = base_idx.get(("glu", tuple(rec["shape"]), rec["dtype"]))
            if base is not None and rec["planned_q_bytes_one_pass"] \
                    > base["planned_q_bytes_one_pass"]:
                print(f"REGRESSION {rec['shape']}/{rec['dtype']}: planned "
                      f"one-pass bytes {rec['planned_q_bytes_one_pass']:.0f} "
                      f"> baseline {base['planned_q_bytes_one_pass']:.0f}")
                failures += 1
            continue
        if rec["kind"] == "quant":
            # Quantization's whole value is the byte ratio: planned int8w
            # bytes must stay at or below the gate vs the bf16 plan, and
            # must never regress vs the committed baseline.
            if rec["planned_ratio"] > QUANT_RATIO_GATE:
                print(f"REGRESSION {rec['shape']}/{rec['dtype']}: planned "
                      f"int8w/bf16 ratio {rec['planned_ratio']:.3f} > "
                      f"{QUANT_RATIO_GATE}")
                failures += 1
            base = base_idx.get(("quant", tuple(rec["shape"]),
                                 rec["dtype"]))
            if base is not None and rec["planned_q_bytes_int8w"] \
                    > base["planned_q_bytes_int8w"]:
                print(f"REGRESSION {rec['shape']}/{rec['dtype']}: planned "
                      f"int8w bytes {rec['planned_q_bytes_int8w']:.0f} > "
                      f"baseline {base['planned_q_bytes_int8w']:.0f}")
                failures += 1
            continue
        if rec["kind"] == "w8a8":
            # w8a8's claim is twofold: the byte ratio must clear the gate
            # (both panels at 1 B/element) and the int8 compute rate must
            # actually halve the roofline's compute term.
            if rec["planned_ratio"] > W8A8_RATIO_GATE:
                print(f"REGRESSION {rec['shape']}/{rec['dtype']}: planned "
                      f"w8a8/bf16 ratio {rec['planned_ratio']:.3f} > "
                      f"{W8A8_RATIO_GATE}")
                failures += 1
            if rec["compute_ratio"] > 0.55:
                print(f"REGRESSION {rec['shape']}/{rec['dtype']}: int8/bf16 "
                      f"compute ratio {rec['compute_ratio']:.3f} > 0.55 — "
                      "the 2x MXU rate is the point of w8a8")
                failures += 1
            base = base_idx.get(("w8a8", tuple(rec["shape"]), rec["dtype"]))
            if base is not None and rec["planned_q_bytes_w8a8"] \
                    > base["planned_q_bytes_w8a8"]:
                print(f"REGRESSION {rec['shape']}/{rec['dtype']}: planned "
                      f"w8a8 bytes {rec['planned_q_bytes_w8a8']:.0f} > "
                      f"baseline {base['planned_q_bytes_w8a8']:.0f}")
                failures += 1
            continue
        if rec["kind"] != "fused_epilogue":
            continue
        if rec["planned_q_bytes_fused"] >= rec["planned_q_bytes_unfused"]:
            print(f"REGRESSION {rec['shape']}/{rec['dtype']}: fused planned "
                  f"bytes not below unfused")
            failures += 1
        base = base_idx.get(("fused_epilogue", tuple(rec["shape"]),
                             rec["dtype"]))
        if base is None:
            continue
        if rec["planned_q_bytes_fused"] > base["planned_q_bytes_fused"]:
            print(f"REGRESSION {rec['shape']}/{rec['dtype']}: planned fused "
                  f"bytes {rec['planned_q_bytes_fused']:.0f} > baseline "
                  f"{base['planned_q_bytes_fused']:.0f}")
            failures += 1
    if not failures:
        print("# baseline check OK (fused planned bytes <= baseline, "
              "< unfused; quant ratio <= gate; w8a8 byte + compute "
              "ratios <= gates; glu ratio <= gate)")
    return failures


def model_error_section(records):
    """Schema-v6 ``model_error``: measured vs model-predicted wall time.

    One entry per record carrying both a ``median_s`` measurement and a
    ``model_predicted_s`` roofline — ``error_ratio`` is measured/planned
    (1.0 = perfect model; >> 1 on this CPU container, where the v5e
    roofline is aspirational).  The geomean across the run is the single
    scalar the perf-model-v2 fit will drive toward 1.0.
    """
    entries = []
    for rec in records:
        med = rec.get("median_s")
        pred = rec.get("model_predicted_s")
        if med is None or pred is None or med <= 0 or pred <= 0:
            continue
        entries.append({
            "kind": rec["kind"],
            "shape": rec["shape"],
            "dtype": rec["dtype"],
            "measured_s": float(med),
            "model_predicted_s": float(pred),
            "error_ratio": float(med) / float(pred),
        })
    section = {"n_entries": len(entries), "entries": entries}
    if entries:
        ratios = np.asarray([e["error_ratio"] for e in entries])
        section["geomean_error_ratio"] = float(np.exp(np.log(ratios).mean()))
        section["min_error_ratio"] = float(ratios.min())
        section["max_error_ratio"] = float(ratios.max())
    return section


def write_json(records, path=DEFAULT_JSON_PATH):
    payload = {
        "schema": JSON_SCHEMA_VERSION,
        "benchmark": "gemm",
        "hardware_model": V5E.name,
        "backend": jax.default_backend(),
        "results": records,
        "model_error": model_error_section(records),
    }
    p = pathlib.Path(path)
    p.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    print(f"# wrote {len(records)} records to {p}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tuned", action="store_true",
                    help="run the empirical autotuner vs the analytic plan")
    ap.add_argument("--sizes", type=int, nargs="+", default=[128, 256],
                    help="square GEMM sizes for --tuned timing")
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--max-candidates", type=int, default=4)
    ap.add_argument("--json", default=DEFAULT_JSON_PATH,
                    help="output path for machine-readable results "
                         "('' disables)")
    ap.add_argument("--baseline", default=DEFAULT_JSON_PATH,
                    help="committed baseline JSON to print deltas against")
    ap.add_argument("--check-baseline", action="store_true",
                    help="exit nonzero if the fused path regresses planned "
                         "bytes vs the baseline (CI gate)")
    ap.add_argument("--skip-fused", action="store_true",
                    help="skip the fused-epilogue section")
    ap.add_argument("--skip-quant", action="store_true",
                    help="skip the int8-weight quantized section")
    ap.add_argument("--skip-w8a8", action="store_true",
                    help="skip the static-activation int8xint8 section")
    ap.add_argument("--skip-glu", action="store_true",
                    help="skip the one-pass SwiGLU program section")
    args = ap.parse_args(argv)
    if any(s <= 0 for s in args.sizes):
        ap.error(f"--sizes must be positive, got {args.sizes}")
    if args.iters <= 0 or args.max_candidates <= 0:
        ap.error("--iters and --max-candidates must be positive")

    base_idx = {}
    try:
        base_idx = _baseline_index(
            json.loads(pathlib.Path(args.baseline).read_text()))
    except (OSError, ValueError):
        if args.check_baseline:
            print(f"# no readable baseline at {args.baseline!r}; the gate "
                  "checks only the fused-vs-unfused invariant")

    records = []
    run(records=records)
    if not args.skip_fused:
        run_fused(records=records, base_idx=base_idx)
    if not args.skip_quant:
        run_quant(records=records, base_idx=base_idx)
    if not args.skip_w8a8:
        run_w8a8(records=records, base_idx=base_idx)
    if not args.skip_glu:
        run_glu(records=records, base_idx=base_idx)
    if args.tuned:
        run_tuned(sizes=tuple(args.sizes), iters=args.iters,
                  max_candidates=args.max_candidates, records=records,
                  base_idx=base_idx)
    rc = 0
    if args.check_baseline:
        rc = check_baseline(records, base_idx)
    if args.json:
        write_json(records, args.json)
    return rc


if __name__ == "__main__":
    sys.exit(main())

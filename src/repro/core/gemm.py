"""Public matmul API: every dense contraction in the framework funnels here.

``ca_matmul`` applies the paper's planned, communication-avoiding schedule:

* mode "pallas"    — the Pallas kernel compiled for TPU (production path).
* mode "interpret" — the same kernel body interpreted on CPU (tests).
* mode "xla"       — ``jnp.dot`` fallback; numerically the oracle, used on
  this CPU container for model smoke tests/examples, and on TPU for shapes
  the planner deems too small to benefit.

Bias / activation / GLU-gate / residual consumers of the GEMM output pass
an :class:`Epilogue`: on the kernel paths the elementwise chain executes
inside the drain phase (riding the single mandatory write-back of paper
Sec. 4.4 — zero extra output traffic); on the XLA path the same fp32
reference semantics apply, so numerics are mode-independent.

The *plan* (tile solve) is computed in all modes, so the I/O model is part
of the traced program's metadata regardless of backend, and the dry-run /
benchmarks can report planned Q alongside compiled HLO bytes.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hardware import TpuTarget, V5E
from repro.core.io_model import TileConfig
from repro.kernels import ops as kops
from repro.kernels.epilogue import Epilogue, IDENTITY, apply_reference
from repro.kernels.program import (GemmProgramSpec, NO_PROLOGUE,
                                   PrologueSpec, RmsPrologue,
                                   apply_rms_reference, rms_row_scale)
from repro.obs.trace import kernel_scope

_state = threading.local()


def set_gemm_mode(mode: str) -> None:
    """Set the global dispatch mode: 'xla' | 'pallas' | 'interpret'."""
    if mode not in ("xla", "pallas", "interpret"):
        raise ValueError(f"unknown gemm mode {mode!r}")
    _state.mode = mode


def get_gemm_mode() -> str:
    return getattr(_state, "mode", "xla")


class gemm_mode:
    """Context manager for temporarily switching dispatch mode."""

    def __init__(self, mode: str):
        self.mode = mode

    def __enter__(self):
        self.prev = get_gemm_mode()
        set_gemm_mode(self.mode)
        return self

    def __exit__(self, *exc):
        set_gemm_mode(self.prev)


# ---------------------------------------------------------------------------
# Kernel-failure fallback (the degradation ladder's first rung)
# ---------------------------------------------------------------------------

_fallback_enabled = False
_fallback_lock = threading.Lock()


def set_gemm_fallback(enabled: bool) -> None:
    """Enable/disable the kernel-failure -> XLA-oracle re-dispatch.

    Off (the default) a kernel-path failure propagates to the caller, so
    a kernel the chip's compiler refuses can never be served silently by
    the oracle.  On (what the chaos tests and ``examples/serve_lm.py
    --chaos`` turn on) a Pallas compile/execute failure — or an injected
    :class:`~repro.runtime.fault.InjectedKernelFailure` — is counted in
    ``gemm.fallback_total{stage}`` and the same GEMM re-runs on the XLA
    oracle path with identical semantics.
    """
    global _fallback_enabled
    with _fallback_lock:
        _fallback_enabled = bool(enabled)


def gemm_fallback_enabled() -> bool:
    return _fallback_enabled


class gemm_fallback:
    """Context manager for temporarily switching the fallback policy."""

    def __init__(self, enabled: bool):
        self.enabled = enabled

    def __enter__(self):
        self.prev = gemm_fallback_enabled()
        set_gemm_fallback(self.enabled)
        return self

    def __exit__(self, *exc):
        set_gemm_fallback(self.prev)


def _fault_check(stage: str) -> None:
    """Chaos hook: raise the active FaultPlan's scheduled failure for
    this dispatch, if any.  Zero-cost until ``repro.runtime.fault`` has
    been imported (a plan cannot exist before its module loads)."""
    import sys

    fault = sys.modules.get("repro.runtime.fault")
    if fault is None:
        return
    plan = fault.active_fault_plan()
    if plan is not None:
        plan.check_gemm(stage)


def _note_fallback(stage: str, exc: Exception) -> None:
    """Account a kernel-dispatch failure and authorize the XLA
    re-dispatch — or re-raise when the failure is fatal (an injected
    ``fatal=True``) or the fallback policy is off."""
    if getattr(exc, "fatal", False) or not _fallback_enabled:
        raise exc
    from repro.obs.metrics import get_metrics  # lazy: obs imports core

    get_metrics().counter(
        "gemm.fallback_total",
        "Kernel-path GEMM dispatch failures re-dispatched on the XLA "
        "oracle path, by dispatch stage").labels(stage=stage).inc()


def _fault_check_xla(stage: str) -> None:
    """Fault hook on the XLA dispatch path: an injected recoverable
    failure counts as a fallback (the 're-dispatch' is the XLA path we
    are already on); a fatal one propagates."""
    try:
        _fault_check(stage)
    except Exception as e:
        _note_fallback(stage, e)


def plan_for(m: int, n: int, k: int, dtype, hw: TpuTarget = V5E,
             epilogue: str = "none", layout: str = "nn",
             dtype_b=None) -> TileConfig:
    """Resolve the tile plan through the kernel-config registry.

    Precedence is cache hit > autotune (if ``REPRO_AUTOTUNE=1``) > the
    analytic :func:`solve_tile_config` — so by default this is exactly the
    paper's model, and a tuned deployment transparently serves measured
    configs.  ``epilogue`` (spec tag) and ``layout`` ('nn'/'nt'/'tn') key
    fused and transpose-streaming kernels distinctly; ``dtype_b`` keys a
    mixed-precision (quantized-weight) GEMM under its composite dtype
    (``"int8w_bf16a"``).
    """
    from repro.tuning import get_registry  # lazy: tuning imports kernels

    return get_registry().resolve(m, n, k, dtype=dtype, hw=hw,
                                  epilogue=epilogue, layout=layout,
                                  dtype_b=dtype_b)


def _ledger():
    """The process-global GEMM ledger (imported lazily — ``repro.obs``
    imports ``repro.core`` for the io_model, not the other way around)."""
    from repro.obs.ledger import get_ledger

    return get_ledger()


def _preflight(res, tag: str, hw: TpuTarget, *, dtype, dtype_b=None,
               dtype_a=None, scale_block: int = 0,
               act_block: int = 0) -> None:
    """Statically verify the resolved plan before launching the kernel.

    Memoized per (resolution key, tile, operand metadata) — the steady
    state pays a dict lookup.  An infeasible plan (e.g. a poisoned cache
    entry over the VMEM budget) raises ``ProgramValidationError`` with
    the full diagnostic list; the error is ``fatal``, so it propagates
    through ``_note_fallback`` instead of being served by the oracle.
    """
    from repro.analyze.preflight import preflight_gemm  # lazy: analyze imports core

    preflight_gemm(res.key, tag, res.config, hw, dtype=dtype,
                   dtype_b=dtype_b, dtype_a=dtype_a,
                   scale_block=scale_block, act_block=act_block)


@kernel_scope("gemm")
def dist_local_matmul(a, b, *, tile: Optional[TileConfig] = None,  # repro: noqa RPR002 -- dist_matmul records once per collective dispatch
                      mode: Optional[str] = None, acc_dtype=jnp.float32):
    """One ring-step local GEMM of a distributed schedule.

    Called from inside ``core.distributed``'s ``shard_map`` bodies with
    the tile the dispatch already resolved (keyed by the per-device local
    shape), so no per-step registry/ledger work happens here.  Kernel
    modes route the float partial through the Pallas CA kernel; a kernel
    failure falls back to the XLA dot under the usual policy (counted in
    ``gemm.fallback_total{stage="dist_local"}``).  ``mode`` is captured
    by the caller at dispatch (trace) time — thread-local state must not
    be read inside a traced body.
    """
    mode = mode or get_gemm_mode()
    if (mode in ("pallas", "interpret") and tile is not None
            and not jnp.issubdtype(a.dtype, jnp.integer)):
        try:
            _fault_check(f"dist_local.{mode}")
            return kops.fused_matmul(
                a, b, tile=tile, interpret=(mode == "interpret"),
                out_dtype=acc_dtype)
        except Exception as e:
            _note_fallback("dist_local", e)
    return jnp.dot(a, b, preferred_element_type=acc_dtype)


def _quant_matmul_tag(epi_spec, prologue, act_scale):
    """The program tag :func:`repro.kernels.ops.quant_matmul` will build
    for these inputs, mirrored here so dispatch resolves the plan exactly
    once and the ledger attributes it.  Returns ``(tag, dtype_a)`` —
    ``dtype_a`` is int8 on the w8a8 ("ab") path.  A static activation
    scale forces the norm out of the program (the rms prologue cannot
    decorate an int8 stream), matching the kernel path's normalization
    fold."""
    deq = "ab" if act_scale is not None else "b"
    spec = dataclasses.replace(epi_spec, dequant=deq)
    pro = PrologueSpec(kind="rms") if (prologue is not None
                                      and act_scale is None) else NO_PROLOGUE
    tag = GemmProgramSpec(prologue=pro, branches=(spec,)).tag()
    return tag, (jnp.int8 if deq == "ab" else None)


def _quant_glu_tag(prologue, act_scale, activation):
    """Same mirror for :func:`repro.kernels.ops.quant_glu_matmul`."""
    deq = "ab" if act_scale is not None else "b"
    branch = dataclasses.replace(IDENTITY, dequant=deq)
    pro = PrologueSpec(kind="rms") if (prologue is not None
                                      and act_scale is None) else NO_PROLOGUE
    tag = GemmProgramSpec(prologue=pro, branches=(branch, branch),
                          combine="glu", combine_activation=activation).tag()
    return tag, (jnp.int8 if deq == "ab" else None)


def _flatten_epilogue(epilogue: Optional[Epilogue], lead, m: int, n: int):
    """Collapse leading batch dims of the (..., n) epilogue operands."""
    if epilogue is None:
        return None
    mul = epilogue.mul
    residual = epilogue.residual
    if mul is not None:
        assert mul.shape[-1] == n, (mul.shape, n)
        mul = mul.reshape(m, n)
    if residual is not None:
        assert residual.shape[-1] == n, (residual.shape, n)
        residual = residual.reshape(m, n)
    return Epilogue(bias=epilogue.bias, activation=epilogue.activation,
                    mul=mul, residual=residual)


def _apply_rms_xla(x: jax.Array, prologue: RmsPrologue) -> jax.Array:
    """Oracle semantics of the rms prologue on the XLA dispatch path —
    the exact elementwise chain of ``models.common.rms_norm``."""
    return apply_rms_reference(x, rms_row_scale(x, prologue.eps),
                               prologue.gain)


def _maybe_record_activation(quant, x: jax.Array,
                             prologue: Optional[RmsPrologue]) -> None:
    """Stream this GEMM's input activation to an active calibration
    context (the w8a8 observe phase).  The recorded tensor is what the
    serve path will actually quantize: the *normalized* activation when
    an rms prologue precedes the projection."""
    from repro.quant.calibrate import active_calibration

    ctx = active_calibration()
    if ctx is None or quant is None:
        return
    xo = _apply_rms_xla(x, prologue) if prologue is not None else x
    ctx.record(quant.shape, xo)


@kernel_scope("gemm")
def ca_matmul(
    x: jax.Array,
    w=None,
    *,
    out_dtype=None,
    hw: TpuTarget = V5E,
    mode: Optional[str] = None,
    epilogue: Optional[Epilogue] = None,
    quant=None,
    prologue: Optional[RmsPrologue] = None,
) -> jax.Array:
    """``epilogue(x @ w)`` with leading batch dims collapsed into the GEMM
    m-dim.

    x: (..., K), w: (K, N) -> (..., N).  This covers the projections, FFNs,
    expert matmuls and logit heads of every architecture in configs/.

    ``prologue`` (an :class:`RmsPrologue`) folds rms_norm into the x-tile
    fetch on the kernel paths — the normalized activation tensor never
    materializes in HBM; the XLA mode applies the identical fp32
    reference chain up front, so numerics are mode-independent.

    A quantized weight — ``quant=QTensor`` or ``w`` itself being a
    :class:`repro.quant.QTensor` (the form checkpoint-quantized param
    trees arrive in) — routes through the scaled-GEMM path: int8 tiles
    stream from HBM and the dequant runs inside the drain as an epilogue
    stage, so only the streamed bytes change (~0.5x of bf16 for the
    weight panel), never the number of HBM round trips.  A QTensor
    additionally carrying a calibrated ``act_scale`` (see
    ``repro.quant.attach_act_scales``) serves **w8a8**: the activation is
    quantized on entry with the static scale and the kernel runs the
    int8xint8 ("ab") path — the MXU's 2x int8 compute rate, not just the
    byte win.  The XLA mode dequantizes the weight up front and applies
    the same quantize-dequantize round trip to the activation (numerics
    oracle of the served math; no byte savings).
    """
    from repro.quant.scales import QTensor, fake_quant_activation

    if quant is None and isinstance(w, QTensor):
        quant = w
    mode = mode or get_gemm_mode()
    if quant is not None:
        assert quant.ndim == 2, quant.shape
        w = None
        k_w, n = quant.shape
    else:
        k_w, n = w.shape
    assert x.shape[-1] == k_w, (x.shape, k_w, n)
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    k = x.shape[-1]
    m = 1
    for d in lead:
        m *= d

    _maybe_record_activation(quant, x, prologue)
    act_scale = quant.act_scale if quant is not None else None

    if quant is not None and (mode == "xla" or m == 0
                              or quant.fmt != "int8"):
        # Oracle path: dequantize (weight-sized fp copy — fine on the XLA
        # fallback, defeats the purpose on a kernel path) then plain GEMM.
        # A static-activation weight applies the identical
        # quantize-dequantize round trip to x, so this stays the exact
        # oracle of the w8a8 kernel's math.
        if m > 0:
            _fault_check_xla("quant_matmul")
        led = _ledger()
        if led.enabled and quant.fmt == "int8" and m > 0:
            # Record under the program the kernel path *would* serve —
            # the plan (and its planned bytes) is backend-independent.
            tag, dtype_a = _quant_matmul_tag(
                epilogue.spec() if epilogue is not None else IDENTITY,
                prologue, act_scale)
            led.record_gemm(
                m, n, k, x.dtype, tag=tag, mode=mode, hw=hw,
                dtype_b=jnp.int8, dtype_a=dtype_a, out_dtype=out_dtype,
                scale_a_elements=(int(np.size(act_scale))
                                  if act_scale is not None else 0),
                scale_b_elements=int(np.size(quant.scale)))
        if prologue is not None:
            x = _apply_rms_xla(x, prologue)
        if act_scale is not None and quant.fmt == "int8":
            x = fake_quant_activation(x, act_scale, quant.act_block)
        z = jnp.dot(x, quant.dequantize(x.dtype),
                    preferred_element_type=jnp.float32)
        if epilogue is not None:
            z = apply_reference(z, epilogue.spec(), epilogue.operands())
        return z.astype(out_dtype)

    if quant is not None:
        x_in, pro_in = x, prologue
        try:
            _fault_check("quant_matmul")
            if act_scale is not None and prologue is not None:
                # The norm cannot ride an int8 stream: apply its reference
                # chain up front, then quantize the normalized activation.
                x = _apply_rms_xla(x, prologue)
                prologue = None
            x2 = x.reshape(m, k)
            epi2 = _flatten_epilogue(epilogue, lead, m, n)
            # Plan here (not in ops) so the resolution happens exactly once
            # and the ledger can attribute it; the tag mirrors the one
            # quant_matmul builds, and the serve dtype is the *float* x
            # dtype (ops quantizes after computing its key the same way).
            from repro.tuning import get_registry  # lazy: tuning imports kernels

            tag, dtype_a = _quant_matmul_tag(
                epi2.spec() if epi2 is not None else IDENTITY,
                prologue, act_scale)
            res = get_registry().resolve_full(m, n, k, dtype=x.dtype, hw=hw,
                                              epilogue=tag, dtype_b=jnp.int8,
                                              dtype_a=dtype_a)
            _preflight(res, tag, hw, dtype=x.dtype, dtype_b=jnp.int8,
                       dtype_a=dtype_a, scale_block=quant.block or 0,
                       act_block=quant.act_block or 0)
            led = _ledger()
            if led.enabled:
                led.record_gemm(
                    m, n, k, x.dtype, tag=tag, mode=mode, hw=hw,
                    dtype_b=jnp.int8, dtype_a=dtype_a, out_dtype=out_dtype,
                    scale_a_elements=(int(np.size(act_scale))
                                      if act_scale is not None else 0),
                    scale_b_elements=int(np.size(quant.scale)),
                    resolution=res)
            y2 = kops.quant_matmul(x2, quant, epi2, res.config,
                                   interpret=(mode == "interpret"),
                                   out_dtype=out_dtype, hw=hw,
                                   prologue=prologue,
                                   act_scale=act_scale,
                                   act_block=quant.act_block)
        except Exception as e:
            _note_fallback("quant_matmul", e)
            return ca_matmul(x_in, out_dtype=out_dtype, hw=hw, mode="xla",
                             epilogue=epilogue, quant=quant,
                             prologue=pro_in)
        return y2.reshape(*lead, n).astype(out_dtype)

    if mode == "xla" or m == 0:
        if m > 0:
            _fault_check_xla("matmul")
        led = _ledger()
        if led.enabled and m > 0 and not jnp.issubdtype(x.dtype,
                                                        jnp.integer):
            tag = GemmProgramSpec(
                prologue=PrologueSpec(kind="rms") if prologue is not None
                else NO_PROLOGUE,
                branches=(epilogue.spec() if epilogue is not None
                          else IDENTITY,)).tag()
            led.record_gemm(m, n, k, x.dtype, tag=tag, mode=mode, hw=hw,
                            out_dtype=out_dtype)
        if prologue is not None:
            x = _apply_rms_xla(x, prologue)
        acc = jnp.float32 if not jnp.issubdtype(x.dtype, jnp.integer) else jnp.int32
        z = jnp.dot(x, w.astype(x.dtype) if acc != jnp.int32 else w,
                    preferred_element_type=acc)
        if epilogue is not None:
            z = apply_reference(z, epilogue.spec(), epilogue.operands())
        return z.astype(out_dtype)

    try:
        _fault_check("matmul")
        x2 = x.reshape(m, k)
        epi2 = _flatten_epilogue(epilogue, lead, m, n)
        # Plan here (not in ops) so the caller's hw target reaches the
        # registry; the key carries the full program tag (prologue
        # included).
        from repro.tuning import get_registry  # lazy: tuning imports kernels

        tag = GemmProgramSpec(
            prologue=PrologueSpec(kind="rms") if prologue is not None
            else NO_PROLOGUE,
            branches=(epi2.spec() if epi2 is not None else IDENTITY,)).tag()
        res = get_registry().resolve_full(m, n, k, dtype=x.dtype, hw=hw,
                                          epilogue=tag)
        _preflight(res, tag, hw, dtype=x.dtype)
        led = _ledger()
        if led.enabled:
            led.record_gemm(m, n, k, x.dtype, tag=tag, mode=mode, hw=hw,
                            out_dtype=out_dtype, resolution=res)
        y2 = kops.fused_matmul(x2, w, epi2, res.config,
                               interpret=(mode == "interpret"),
                               out_dtype=out_dtype, prologue=prologue)
    except Exception as e:
        _note_fallback("matmul", e)
        return ca_matmul(x, w, out_dtype=out_dtype, hw=hw, mode="xla",
                         epilogue=epilogue, prologue=prologue)
    return y2.reshape(*lead, n).astype(out_dtype)


@kernel_scope("gemm")
def ca_glu_matmul(
    x: jax.Array,
    w_gate,
    w_up,
    *,
    activation: str = "silu",
    out_dtype=None,
    hw: TpuTarget = V5E,
    mode: Optional[str] = None,
    prologue: Optional[RmsPrologue] = None,
) -> jax.Array:
    """``act(x @ Wg) · (x @ Wu)`` as one dual-branch program: the x panel
    streams **once** for both contractions (two VMEM accumulators, one
    drain) — SwiGLU without the separate ``up`` GEMM's write/read or its
    second x stream.  ``prologue`` folds the pre-FFN rms_norm into the
    same fetch.

    Quantized weights (both :class:`repro.quant.QTensor`) stream int8
    with a per-branch drain-fused dequant — per-channel scales drain,
    per-tile (blocked) scales rescale every branch's k-step partial
    product in the one dual-branch pass.  Weights carrying a calibrated
    ``act_scale`` serve w8a8: the shared x panel is quantized on entry
    (after the norm, which cannot ride an int8 stream) and both branches
    run the int8xint8 ("ab") path.  The XLA mode applies the identical
    fp32 reference chain, activation quantize-dequantize included
    (numerics oracle).
    """
    from repro.quant.scales import QTensor, fake_quant_activation

    mode = mode or get_gemm_mode()
    quantized = isinstance(w_gate, QTensor)
    assert quantized == isinstance(w_up, QTensor), \
        "quantize both GLU weights or neither"
    k_w, n = w_gate.shape
    assert x.shape[-1] == k_w and tuple(w_up.shape) == (k_w, n), \
        (x.shape, w_gate.shape, w_up.shape)
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    k = x.shape[-1]
    m = 1
    for d in lead:
        m *= d

    act_scale = act_block = None
    if quantized:
        _maybe_record_activation(w_gate, x, prologue)
        act_scale, act_block = w_gate.act_scale, w_gate.act_block

    stage = "quant_glu" if quantized else "glu"
    kernel_ok = mode != "xla" and m > 0 and \
        (not quantized or (w_gate.fmt == "int8" and w_up.fmt == "int8"))
    if not kernel_ok:
        if m > 0:
            _fault_check_xla(stage)
        led = _ledger()
        if led.enabled and m > 0 and \
                (not quantized or (w_gate.fmt == "int8"
                                   and w_up.fmt == "int8")):
            if quantized:
                tag, dtype_a = _quant_glu_tag(prologue, act_scale,
                                              activation)
                led.record_gemm(
                    m, n, k, x.dtype, tag=tag, mode=mode, hw=hw,
                    dtype_b=jnp.int8, dtype_a=dtype_a, out_dtype=out_dtype,
                    scale_a_elements=(int(np.size(act_scale))
                                      if act_scale is not None else 0),
                    scale_b_elements=(int(np.size(w_gate.scale))
                                      + int(np.size(w_up.scale))))
            else:
                tag = GemmProgramSpec(
                    prologue=PrologueSpec(kind="rms")
                    if prologue is not None else NO_PROLOGUE,
                    branches=(IDENTITY, IDENTITY), combine="glu",
                    combine_activation=activation).tag()
                led.record_gemm(m, n, k, x.dtype, tag=tag, mode=mode,
                                hw=hw, out_dtype=out_dtype)
        if prologue is not None:
            x = _apply_rms_xla(x, prologue)
        if quantized and act_scale is not None and w_gate.fmt == "int8":
            x = fake_quant_activation(x, act_scale, act_block)
        wg = w_gate.dequantize(x.dtype) if quantized else w_gate.astype(x.dtype)
        wu = w_up.dequantize(x.dtype) if quantized else w_up.astype(x.dtype)
        g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
        from repro.kernels.epilogue import act_fn

        return (act_fn(activation)(g) * u).astype(out_dtype)

    x_in, pro_in = x, prologue
    try:
        _fault_check(stage)
        if quantized and act_scale is not None and prologue is not None:
            x = _apply_rms_xla(x, prologue)
            prologue = None
        x2 = x.reshape(m, k)
        interpret = mode == "interpret"
        from repro.tuning import get_registry  # lazy: tuning imports kernels

        led = _ledger()
        if quantized:
            # Resolve here (once) and hand the tile down, mirroring the
            # tag quant_glu_matmul builds; serve dtype is the float x
            # dtype.
            tag, dtype_a = _quant_glu_tag(prologue, act_scale, activation)
            res = get_registry().resolve_full(m, n, k, dtype=x.dtype, hw=hw,
                                              epilogue=tag, dtype_b=jnp.int8,
                                              dtype_a=dtype_a)
            _preflight(res, tag, hw, dtype=x.dtype, dtype_b=jnp.int8,
                       dtype_a=dtype_a,
                       scale_block=w_gate.block or 0,
                       act_block=act_block or 0)
            if led.enabled:
                led.record_gemm(
                    m, n, k, x.dtype, tag=tag, mode=mode, hw=hw,
                    dtype_b=jnp.int8, dtype_a=dtype_a, out_dtype=out_dtype,
                    scale_a_elements=(int(np.size(act_scale))
                                      if act_scale is not None else 0),
                    scale_b_elements=(int(np.size(w_gate.scale))
                                      + int(np.size(w_up.scale))),
                    resolution=res)
            y2 = kops.quant_glu_matmul(x2, w_gate, w_up,
                                       activation=activation,
                                       prologue=prologue, tile=res.config,
                                       interpret=interpret,
                                       out_dtype=out_dtype, hw=hw,
                                       act_scale=act_scale,
                                       act_block=act_block or 0)
        else:
            tag = GemmProgramSpec(
                prologue=PrologueSpec(kind="rms") if prologue is not None
                else NO_PROLOGUE,
                branches=(IDENTITY, IDENTITY), combine="glu",
                combine_activation=activation).tag()
            res = get_registry().resolve_full(m, n, k, dtype=x.dtype, hw=hw,
                                              epilogue=tag)
            _preflight(res, tag, hw, dtype=x.dtype)
            if led.enabled:
                led.record_gemm(m, n, k, x.dtype, tag=tag, mode=mode,
                                hw=hw, out_dtype=out_dtype, resolution=res)
            y2 = kops.glu_matmul(x2, w_gate, w_up, activation=activation,
                                 prologue=prologue, tile=res.config,
                                 interpret=interpret, out_dtype=out_dtype)
    except Exception as e:
        _note_fallback(stage, e)
        return ca_glu_matmul(x_in, w_gate, w_up, activation=activation,
                             out_dtype=out_dtype, hw=hw, mode="xla",
                             prologue=pro_in)
    return y2.reshape(*lead, n).astype(out_dtype)


@kernel_scope("gemm")
def ca_expert_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    out_dtype=None,
    hw: TpuTarget = V5E,
    mode: Optional[str] = None,
) -> jax.Array:
    """Batched expert contraction ``x[..., e, :, :] @ w[e]`` (the MoE
    ``becd,edf -> becf`` einsum) routed per-expert through the registry.

    On kernel paths each expert's GEMM is a registry-planned CA-MMM (the
    expert loop ROADMAP item (d) asked for); the XLA mode keeps the
    batched einsum — the exact oracle the loop is tested against.

    Trade-off, deliberate: the loop traces E kernel instances and slices
    the expert axis per step, so on a *multi-device mesh with the expert
    dim sharded* the einsum/XLA dispatch (the default, and what the
    sharded launch paths use) remains the right choice — GSPMD
    partitions it cleanly across experts, while slicing a sharded axis
    would gather per-expert buffers.  The kernel loop is the
    single-device/serving path; folding it into one vmapped kernel
    launch is ROADMAP follow-on (d2).
    """
    mode = mode or get_gemm_mode()
    E, k_w, n = w.shape
    assert x.shape[-3] == E and x.shape[-1] == k_w, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype
    if mode == "xla" or x.size == 0:
        led = _ledger()
        if led.enabled and x.size > 0:
            # One record covering the whole einsum: E identical per-expert
            # GEMMs (the kernel path records each via its inner ca_matmul).
            led.record_gemm(x.size // (E * k_w), n, k_w, x.dtype,
                            tag="none", mode=mode, hw=hw,
                            out_dtype=out_dtype, calls=E)
        z = jnp.einsum("...ecd,edf->...ecf", x, w,
                       preferred_element_type=jnp.float32)
        return z.astype(out_dtype)
    ys = [ca_matmul(x[..., e, :, :], w[e], out_dtype=out_dtype, hw=hw,
                    mode=mode) for e in range(E)]
    return jnp.stack(ys, axis=-3)


@kernel_scope("gemm")
def ca_expert_glu_matmul(
    x: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    *,
    activation: str = "silu",
    out_dtype=None,
    hw: TpuTarget = V5E,
    mode: Optional[str] = None,
) -> jax.Array:
    """Per-expert dual-branch GLU: each expert's gate/up pair shares one
    pass over that expert's token buffer (the capacity-buffer rows stream
    once, two accumulators per expert GEMM)."""
    mode = mode or get_gemm_mode()
    E, k_w, n = w_gate.shape
    assert x.shape[-3] == E and x.shape[-1] == k_w, (x.shape, w_gate.shape)
    assert w_up.shape == w_gate.shape, (w_up.shape, w_gate.shape)
    out_dtype = out_dtype or x.dtype
    if mode == "xla" or x.size == 0:
        led = _ledger()
        if led.enabled and x.size > 0:
            tag = GemmProgramSpec(branches=(IDENTITY, IDENTITY),
                                  combine="glu",
                                  combine_activation=activation).tag()
            led.record_gemm(x.size // (E * k_w), n, k_w, x.dtype,
                            tag=tag, mode=mode, hw=hw,
                            out_dtype=out_dtype, calls=E)
        from repro.kernels.epilogue import act_fn

        g = jnp.einsum("...ecd,edf->...ecf", x, w_gate,
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("...ecd,edf->...ecf", x, w_up,
                       preferred_element_type=jnp.float32)
        return (act_fn(activation)(g) * u).astype(out_dtype)
    ys = [ca_glu_matmul(x[..., e, :, :], w_gate[e], w_up[e],
                        activation=activation, out_dtype=out_dtype, hw=hw,
                        mode=mode) for e in range(E)]
    return jnp.stack(ys, axis=-3)


def ca_einsum(spec: str, x: jax.Array, w: jax.Array, **kw) -> jax.Array:
    """Einsum wrapper: routes 'matmul-shaped' contractions through
    ca_matmul, everything else through jnp.einsum (fp32 accumulation)."""
    try:
        lhs, out = spec.split("->")
        a_spec, b_spec = lhs.split(",")
    except ValueError:
        return jnp.einsum(spec, x, w, preferred_element_type=jnp.float32, **kw)
    if (len(b_spec) == 2 and a_spec[-1] == b_spec[0]
            and out == a_spec[:-1] + b_spec[1]):
        return ca_matmul(x, w, **kw)
    return jnp.einsum(spec, x, w, preferred_element_type=jnp.float32, **kw)

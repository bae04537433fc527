"""Serving engine: the serving-side integration of the framework.

Requests are served one at a time: a prefill at batch 1, then a decode
loop at batch 1 (``batch_size`` sizes only the warmup shapes and the
KV page pool).
Sampling is greedy or temperature-based and fully deterministic given the
seed.  KV caches are the per-arch pytrees from models/ (compressed MLA
cache, rolling SWA cache, O(1) SSM state — whatever the config dictates).

Fault tolerance (docs/ROBUSTNESS.md): every request is isolated — a
kernel error or non-finite logits fails *that* request
(``serve.requests_failed_total{reason}``) while the rest of the queue
completes.  Admission is bounded (``max_queue`` with reject/shed-oldest
backpressure, ``serve.rejected_total{policy}``), requests carry a queue
TTL and a decode deadline, transient failures retry with exponential
backoff, and non-finite logits walk the per-request quant degradation
ladder w8a8 -> int8w -> dense (``serve.degraded_total{from,to}``).  A
failed startup calibration degrades the engine to weight-only quant
instead of crashing.  All of it is deterministically testable through
:class:`repro.runtime.fault.FaultPlan`.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
import warnings
from typing import Any, Deque, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.obs import get_metrics, span
from repro.obs.ledger import get_ledger
from repro.quant import (ActivationCalibration, QTensor, QuantConfig,
                         attach_act_scales)
from repro.runtime.fault import (InjectedKernelFailure, TransientServeError,
                                 active_fault_plan)
from repro.tuning import warmup_model

# Per-request quant degradation ladder, most- to least-quantized.  A
# request whose logits go non-finite is retried one rung down (dense =
# the config dtype, QTensors dequantized); past the last rung it fails.
QUANT_LEVELS = ("w8a8", "int8w", "dense")

_FAILED_DESC = "Requests failed, by reason (kernel/nonfinite/deadline/...)"
_DEGRADED_DESC = ("Quant degradations, by from/to level (per-request "
                  "ladder steps and engine-init calibration fallback)")
_REJECTED_DESC = "Requests rejected/shed at admission, by policy"
_FALLBACK_DESC = ("Kernel-path GEMM dispatch failures re-dispatched on "
                  "the XLA oracle path, by dispatch stage")
_SYNCS_DESC = ("Device-to-host reads in the token loop, by where (at): "
               "one per output token, at=sample (its token and finite "
               "flag)")
_PUTS_DESC = ("Host-to-device inputs made for the model steps, by what "
              "they carry: the prompt with its temperature, once per "
              "serve attempt; decode steps take the previous step's "
              "device outputs")


class Step(NamedTuple):
    """What a jitted serve step returns, all on the device.  The next
    decode step takes ``token``, ``cache``, ``pos`` and ``key`` as they
    are; the host reads ``token`` and ``finite`` once (``_sample``)."""
    logits: jax.Array    # (1, L, [n_codebooks,] vocab_padded) fp32
    cache: Any
    token: jax.Array     # (1, 1) int32, sampled from the last row
    finite: jax.Array    # () bool, that row all finite
    pos: jax.Array       # () int32, the position of ``token``
    key: jax.Array       # the engine key after ``token``'s draw


def _token_input(cfg: ModelConfig, toks: jax.Array, table):
    """Model input for ``(1, L)`` token ids: the ids, or their rows of
    the demo embedding table for an embeds-frontend config."""
    if cfg.frontend == "tokens":
        return {"tokens": toks}
    return {"embeds": table[toks]}


def _step(cfg: ModelConfig, logits: jax.Array, cache, pos: jax.Array,
          key: jax.Array, temperature: jax.Array) -> Step:
    """A step's :class:`Step`, traced inside it: the finite flag over the
    last row of ``logits`` (every codebook), and the token drawn from
    that row (codebook 0): greedy at ``temperature <= 0``, else one split
    of ``key`` and a categorical draw at ``temperature``."""
    row = logits[0, -1, ..., :cfg.vocab_size]
    finite = jnp.all(jnp.isfinite(row))
    if cfg.n_codebooks > 1:
        row = row[0]

    def greedy(key):
        return jnp.argmax(row).astype(jnp.int32), key

    def draw(key):
        key, sub = jax.random.split(key)
        tok = jax.random.categorical(sub, row / temperature)
        return tok.astype(jnp.int32), key

    tok, key = jax.lax.cond(temperature > 0, draw, greedy, key)
    return Step(logits, cache, tok.reshape(1, 1), finite, pos, key)


class NonFiniteLogits(RuntimeError):
    """Sampled logits contained NaN/Inf — the quant-degradation trigger."""


class DeadlineExceeded(RuntimeError):
    """A request ran past its decode deadline."""


def _next_level(level: str) -> Optional[str]:
    i = QUANT_LEVELS.index(level)
    return QUANT_LEVELS[i + 1] if i + 1 < len(QUANT_LEVELS) else None


def _is_quantized(params) -> bool:
    leaves = jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, QTensor))
    return any(isinstance(l, QTensor) for l in leaves)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (Lp,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    generated: Optional[List[int]] = None
    # -- lifecycle ----------------------------------------------------------
    # pending -> queued -> running -> done | degraded | failed; rejected
    # requests (admission) never run.  ``degraded`` is a *successful*
    # terminal state: the output exists but was served below the engine's
    # base quant level and/or through a GEMM fallback.
    status: str = "pending"
    error: Optional[str] = None
    deadline_s: Optional[float] = None   # decode wall-clock budget (dequeue-relative)
    queue_ttl_s: Optional[float] = None  # max submit()->dequeue wait
    max_retries: int = 0                 # transient-failure retry budget
    attempts: int = 0                    # serve attempts consumed
    quant_level: Optional[str] = None    # level of the last attempt
    degraded_to: Optional[str] = None    # set when the ladder stepped down
    fallbacks: int = 0                   # GEMM->XLA fallbacks during serving


class ServeEngine:
    """Single-host engine that serves one request at a time at batch 1
    (ROADMAP S4 brings batching)."""

    def __init__(self, params, cfg: ModelConfig, *, batch_size: int,
                 max_len: int, seed: int = 0, warmup_gemms: bool = True,
                 quantize_activations: bool = False,
                 calibration_batches: int = 4,
                 act_qconfig: Optional[QuantConfig] = None,
                 max_queue: int = 0, overflow: str = "reject",
                 retry_backoff_s: float = 0.05,
                 check_finite: bool = True,
                 paged_kv: bool = False, kv_page_size: int = 0,
                 kv_pool_pages: int = 0, kv_max_pages_per_seq: int = 0,
                 tp_local: Optional[Tuple[int, int]] = None):
        if overflow not in ("reject", "shed_oldest"):
            raise ValueError(f"unknown overflow policy {overflow!r}")
        self.params = params
        self.cfg = cfg
        self.B = batch_size
        self.max_len = max_len
        self.key = jax.random.PRNGKey(seed)
        self.quantized = _is_quantized(params)
        self.max_queue = max_queue          # 0 = unbounded admission
        self.overflow = overflow
        self.retry_backoff_s = retry_backoff_s
        self.check_finite = check_finite
        # Static activation quantization (w8a8): run a calibration pass
        # over sample traffic *before* warmup and jit — every projection
        # site's activation distribution is observed, its static a-scale
        # is attached to the weight QTensor, and every GEMM the jitted
        # steps trace thereafter takes the int8xint8 ("ab") kernel path:
        # the MXU's 2x int8 compute rate on top of PR 3's byte win.
        # A calibration failure (e.g. an empty percentile reservoir)
        # degrades the engine to weight-only quant instead of aborting
        # startup — counted in serve.degraded_total{from=w8a8,to=int8w}.
        self.w8a8 = False
        self.calibration_sites: List[str] = []
        metrics = get_metrics()
        if quantize_activations:
            if not self.quantized:
                raise ValueError(
                    "quantize_activations requires weight-quantized "
                    "params (models.common.quantize_params first)")
            self.act_qconfig = act_qconfig or QuantConfig(act_fmt="int8")
            if not self.act_qconfig.quantize_activations:
                raise ValueError("act_qconfig has no activation format: "
                                 f"{self.act_qconfig}")
            t0 = time.perf_counter()
            try:
                with span("serve.calibrate", batches=calibration_batches):
                    self.params = self._calibrate_activations(
                        calibration_batches)
                self.w8a8 = True
            except Exception as e:  # repro: noqa RPR004 -- documented degradation: w8a8 -> int8w, counted in serve.degraded_total
                warnings.warn(
                    f"activation calibration failed ({e!r}); degrading "
                    "engine to weight-only int8 serving", RuntimeWarning)
                metrics.counter("serve.degraded_total",
                                _DEGRADED_DESC).labels(
                    **{"from": "w8a8", "to": "int8w"}).inc()
            metrics.gauge(
                "serve.calibration_seconds",
                "Wall time of the w8a8 static-activation calibration "
                "pass").set(time.perf_counter() - t0)
        # Serve-time warmup: resolve every hot-path GEMM tile through the
        # kernel-config registry (cache > autotune > analytic) before the
        # first request, so no request pays tuning/solver latency.  The
        # workload set carries each GEMM's (program_tag, layout) variant
        # — the dense FFN's rms-prologue-fused dual-branch GLU program,
        # the per-expert GLU/down programs of MoE archs, and residual
        # drains all plan under their own keys; a weight-quantized param
        # tree warms the int8-weight variants instead (per-branch dequant
        # tags like ``glu.silu(dqb|dqb)``, ``int8w_*`` dtype keys), and a
        # w8a8 engine the static-activation variants (``dqab`` tags,
        # ``int8w_int8a`` keys, no rms prologue — the norm runs via XLA
        # before the quantize-on-entry), since those are the kernels its
        # projections will issue.  The jitted prefill/decode steps below
        # fetch the same configs at trace time.
        quant_mode = "w8a8" if self.w8a8 else self.quantized
        t0 = time.perf_counter()
        with span("serve.warmup", quant=str(quant_mode)):
            self.gemm_plan_sources = (
                warmup_model(cfg, [batch_size, batch_size * max_len],
                             quant=quant_mode)
                if warmup_gemms else {})
            # A tensor-parallel engine additionally warms the *local*
            # ring-step shapes its projections resolve when dispatched
            # through core.distributed.dist_matmul — tp_local=(dp, tp)
            # rewrites every workload to (ceil(m/dp), n/tp, k/tp).
            if warmup_gemms and tp_local is not None:
                self.gemm_plan_sources.update(
                    warmup_model(cfg, [batch_size, batch_size * max_len],
                                 quant=quant_mode, shard=tp_local))
        metrics.gauge(
            "serve.warmup_seconds",
            "Wall time of the GEMM plan warmup (registry prewarm)").set(
                time.perf_counter() - t0)
        plan_counter = metrics.counter(
            "serve.gemm_plan_total",
            "Warmup-resolved GEMM plans by source (cache/autotune/"
            "analytic)")
        for src in self.gemm_plan_sources.values():
            plan_counter.labels(source=src).inc()
        # Named steps: the compiled modules read ``jit_serve_prefill`` /
        # ``jit_serve_decode`` in a device trace, and every op's name
        # path starts with ``jit(serve_decode)``.  Each step also samples
        # its next token and checks that row for NaN/Inf (``Step``), so
        # the next decode step starts from device values alone.
        # ``temperature`` is traced: one program serves every request.
        def serve_prefill(p, toks, key, temperature, table):
            logits, cache = M.prefill(p, _token_input(cfg, toks, table),
                                      cfg, max_len=max_len)
            return _step(cfg, logits, cache, jnp.int32(toks.shape[1]), key,
                         temperature)

        def serve_decode(p, tok, c, pos, key, temperature, table):
            logits, cache = M.decode_step(
                p, _token_input(cfg, tok, table), c, pos, cfg)
            return _step(cfg, logits, cache, pos + 1, key, temperature)

        self._prefill = jax.jit(serve_prefill)
        self._decode = jax.jit(serve_decode)
        # Paged KV mode (docs/KVCACHE.md): variable-length sequences admit
        # against a host-side page pool instead of a max_len-sized slab;
        # int8 pages + per-page scales replace the serve-dtype cache.  The
        # page size resolves through the registry like every GEMM tile
        # (the paged_decode attention entry's kv_block *is* the page).
        self.kv_pool = None
        self.attn_plan_sources: Dict[str, str] = {}
        if paged_kv:
            if (cfg.attn_kind != "gqa"
                    or cfg.family in ("ssm", "hybrid")
                    or cfg.shared_attn_every):
                raise ValueError(
                    "paged KV serving needs a plain GQA transformer "
                    f"(got attn={cfg.attn_kind}, family={cfg.family}) "
                    "[KV005]")
            from repro import kvcache as kvc
            from repro.tuning import resolve_page_size, warmup_attention

            self._kvc = kvc
            t0 = time.perf_counter()
            with span("serve.attn_warmup", paged=True):
                self.attn_plan_sources = warmup_attention(
                    cfg, max_len, paged=True)
            metrics.gauge(
                "serve.attn_warmup_seconds",
                "Wall time of the attention blocking warmup").set(
                    time.perf_counter() - t0)
            if not kv_page_size:
                res = resolve_page_size(
                    heads=cfg.n_heads, kv_heads=cfg.n_kv_heads,
                    head_dim=cfg.resolved_head_dim, seq_len=max_len)
                kv_page_size = res.config.kv_block
            per_seq = -(-max_len // kv_page_size)
            self.kv_max_pages_per_seq = kv_max_pages_per_seq or per_seq
            self.kv_pool = kvc.PagePool(
                kv_pool_pages or batch_size * per_seq, kv_page_size)
            metrics.gauge(
                "serve.kv_pool_pages",
                "Page count of the serve KV pool").set(self.kv_pool.n_pages)
            self.kv_cache = M.make_paged_model_cache(
                cfg, 1, n_pages=self.kv_pool.n_pages,
                page_size=kv_page_size, max_pages=self.kv_max_pages_per_seq)

            def serve_prefill_paged(p, toks, c, key, temperature, table):
                logits, cache = M.prefill(
                    p, _token_input(cfg, toks, table), cfg,
                    max_len=max_len, cache=c)
                return _step(cfg, logits, cache, jnp.int32(toks.shape[1]),
                             key, temperature)

            self._prefill_paged = jax.jit(serve_prefill_paged)
        self.base_level = ("w8a8" if self.w8a8
                           else "int8w" if self.quantized else "dense")
        self._level_params: Dict[str, object] = {self.base_level: self.params}
        self.queue: Deque[Request] = collections.deque()
        self.done: Dict[int, Request] = {}
        self._submit_t: Dict[int, float] = {}

    @functools.cached_property
    def _table(self) -> Optional[jax.Array]:
        """Deterministic demo embedding table of an embeds-frontend config
        (seed 0, the historical convention), built once and passed to
        every step; None for a tokens frontend."""
        if self.cfg.frontend == "tokens":
            return None
        return jnp.asarray(
            np.random.RandomState(0).randn(self.cfg.vocab_size,
                                           self.cfg.d_model) * 0.02,
            self.cfg.dtype())

    def _sample_inputs(self, rng: np.random.RandomState, length: int):
        """One prefill input of sample traffic (tokens or embeds)."""
        return _token_input(self.cfg, jnp.asarray(
            rng.randint(0, self.cfg.vocab_size, (1, length)), jnp.int32),
            self._table)

    def _calibrate_activations(self, n_batches: int):
        """The classic post-training static calibration loop: forward a
        few sample batches with an :class:`ActivationCalibration` context
        recording every quantized projection's input, then write the
        resulting static a-scales onto the weight QTensors.

        Runs the un-jitted forward on the XLA dispatch path (recording
        rides ``io_callback``, so the ``lax.scan``-stacked layers are
        observed too); the jitted serve steps trace afterwards, against
        the already-annotated params.
        """
        rng = np.random.RandomState(1234)
        length = max(2, min(8, self.max_len - 1))
        with ActivationCalibration(self.act_qconfig) as ctx:
            for _ in range(max(1, n_batches)):
                pre_in = self._sample_inputs(rng, length)
                jax.block_until_ready(
                    M.prefill(self.params, pre_in, self.cfg,
                              max_len=self.max_len)[0])
        self.calibration_sites = sorted(ctx.calibrators)
        return attach_act_scales(self.params, ctx.scales(),
                                 block=self.act_qconfig.act_block)

    # -- degradation ladder -------------------------------------------------

    def _params_for(self, level: str):
        """The param tree serving quant ``level`` (built lazily, cached).

        ``int8w`` strips the calibrated ``act_scale`` from every QTensor
        (weight-only int8); ``dense`` dequantizes every QTensor to the
        config dtype.  The jitted steps retrace per distinct tree
        structure, so a degraded retry pays one compile, not a new
        engine.
        """
        params = self._level_params.get(level)
        if params is not None:
            return params
        is_q = lambda x: isinstance(x, QTensor)  # noqa: E731
        base = self._level_params[self.base_level]
        if level == "int8w":
            params = jax.tree.map(
                lambda l: dataclasses.replace(l, act_scale=None,
                                              act_block=0)
                if is_q(l) and l.act_scale is not None else l,
                base, is_leaf=is_q)
        elif level == "dense":
            dt = self.cfg.dtype()
            params = jax.tree.map(
                lambda l: l.dequantize(dt) if is_q(l) else l,
                base, is_leaf=is_q)
        else:
            raise ValueError(f"cannot degrade to level {level!r}")
        self._level_params[level] = params
        return params

    # -- admission ----------------------------------------------------------

    def submit(self, req: Request) -> bool:
        """Admit a request (True) or reject/shed under backpressure.

        With ``max_queue`` set, a full queue either rejects the new
        request (``overflow="reject"``) or sheds the oldest queued one to
        admit it (``overflow="shed_oldest"``); both outcomes land in
        ``done`` with status ``"rejected"`` and count
        ``serve.rejected_total{policy}``.
        """
        req.generated = []
        if self.kv_pool is not None:
            # A request that can never hold its worst-case KV footprint
            # (prompt + full generation budget) is rejected up front
            # rather than failing mid-decode with pages half-written.
            need = self.kv_pool.pages_for(
                len(req.prompt) + req.max_new_tokens)
            if need > min(self.kv_pool.n_pages, self.kv_max_pages_per_seq):
                req.status = "rejected"
                req.error = (f"kv pages: need {need} pages, pool holds "
                             f"{self.kv_pool.n_pages} "
                             f"(per-seq cap {self.kv_max_pages_per_seq})")
                get_metrics().counter(
                    "serve.rejected_total", _REJECTED_DESC).labels(
                        policy="kv_pages").inc()
                self.done[req.uid] = req
                return False
        if self.max_queue and len(self.queue) >= self.max_queue:
            rejected = get_metrics().counter("serve.rejected_total",
                                             _REJECTED_DESC)
            if self.overflow == "reject":
                req.status = "rejected"
                req.error = f"queue full ({len(self.queue)}/{self.max_queue})"
                rejected.labels(policy="reject").inc()
                self.done[req.uid] = req
                return False
            old = self.queue.popleft()
            self._submit_t.pop(old.uid, None)
            old.status = "rejected"
            old.error = "shed: queue full and a newer request arrived"
            rejected.labels(policy="shed_oldest").inc()
            self.done[old.uid] = old
        req.status = "queued"
        self.queue.append(req)
        self._submit_t[req.uid] = time.perf_counter()
        return True

    def _sample(self, step: Step, temperature: float) -> int:
        """The token ``step`` sampled at ``temperature``: the one place a
        served token reaches the host, in one read with the step's
        finite flag.  Raises :class:`NonFiniteLogits` when the sampled
        row is poisoned and ``check_finite`` is on.  A drawn token
        consumed one split of the engine's key, which the next request
        starts from; a greedy step hands its key back unchanged.
        ``temperature`` is unused here (the step sampled already) and is
        kept because the benchmark harness's tests patch this method
        with that signature."""
        tok, finite = jax.device_get((step.token, step.finite))
        self._h["sync_sample"].inc()
        if self.check_finite and not finite:
            raise NonFiniteLogits("non-finite logits in sampled row")
        self.key = step.key
        return int(tok[0, 0])

    # -- the serve loop -----------------------------------------------------

    def run(self) -> Dict[int, Request]:
        """Serve everything in the queue, one request at a time: a
        prefill at batch 1, then a decode loop at batch 1.

        Fully instrumented: queue wait, TTFT (dequeue to first token on
        the host), the interval between a request's output tokens
        (TPOT), and the prefill/decode wall split land in the metrics
        registry; each phase runs under a trace span and a GEMM-ledger
        step, so ``metrics_report()`` can state achieved bytes/s against
        the planned I/O model.

        Every request is served under an isolation wrapper: failures
        (kernel errors, non-finite logits past the degradation ladder,
        deadline/TTL overruns, exhausted retries) mark *that* request
        failed and the loop continues with the next one.
        """
        metrics = get_metrics()
        self._h = {
            "queue_wait": metrics.histogram(
                "serve.queue_wait_seconds", "submit() to dequeue latency"),
            "ttft": metrics.histogram(
                "serve.ttft_seconds", "Dequeue to first sampled token"),
            "tpot": metrics.histogram(
                "serve.tpot_seconds",
                "Interval between a request's consecutive output tokens "
                "reaching the host (each decode step is dispatched "
                "before the previous token is read)"),
            "prefill_s": metrics.counter(
                "serve.prefill_seconds_total",
                "Wall time from dequeue to the first token on the host"),
            "decode_s": metrics.counter(
                "serve.decode_seconds_total",
                "Wall time from the first token to the last on the host"),
            "tokens": metrics.counter(
                "serve.tokens_generated_total", "Sampled output tokens"),
            "n_requests": metrics.counter(
                "serve.requests_total", "Requests served to completion"),
            "failed": metrics.counter(
                "serve.requests_failed_total", _FAILED_DESC),
            "degraded": metrics.counter(
                "serve.degraded_total", _DEGRADED_DESC),
            "retries": metrics.counter(
                "serve.retries_total",
                "Transient-failure retries (exponential backoff)"),
            "fallback": metrics.counter(
                "gemm.fallback_total", _FALLBACK_DESC),
        }
        syncs = metrics.counter("serve.host_syncs_total", _SYNCS_DESC)
        puts = metrics.counter("serve.host_puts_total", _PUTS_DESC)
        self._h.update(sync_sample=syncs.labels(at="sample"),
                       put_prompt=puts.labels(what="prompt"))
        tokens = self._h["tokens"]
        t_run = time.perf_counter()
        while self.queue:
            req = self.queue.popleft()
            t_req = time.perf_counter()
            submitted = self._submit_t.pop(req.uid, None)
            if submitted is not None:
                wait = t_req - submitted
                self._h["queue_wait"].observe(wait)
                if req.queue_ttl_s is not None and wait > req.queue_ttl_s:
                    self._finish_failed(
                        req, "queue_ttl",
                        f"queued {wait:.3f}s > ttl {req.queue_ttl_s}s")
                    continue
            req.status = "running"
            self._serve_with_recovery(req, t_req)
        elapsed = time.perf_counter() - t_run
        if elapsed > 0:
            metrics.gauge(
                "serve.tokens_per_second",
                "Output tokens over the last run()'s wall time").set(
                    tokens.value / elapsed)
        return self.done

    def _finish_failed(self, req: Request, reason: str, msg: str) -> None:
        req.status = "failed"
        req.error = f"{reason}: {msg}" if msg else reason
        self._h["failed"].labels(reason=reason).inc()
        self.done[req.uid] = req

    @staticmethod
    def _failure_reason(exc: Exception) -> str:
        if isinstance(exc, InjectedKernelFailure):
            return "kernel"
        if isinstance(exc, DeadlineExceeded):
            return "deadline"
        if isinstance(exc, NonFiniteLogits):
            return "nonfinite"
        if getattr(exc, "transient", False):
            return "transient"
        return type(exc).__name__

    def _serve_with_recovery(self, req: Request, t_req: float) -> None:
        """Serve one request under the isolation wrapper: transient
        failures retry with exponential backoff, non-finite logits walk
        the quant ladder down, everything else fails exactly this
        request.  Terminal status/error/counters are set here."""
        level = self.base_level
        deadline_t = (t_req + req.deadline_s
                      if req.deadline_s is not None else None)
        fb0 = self._h["fallback"].value
        retries = 0
        backoff = self.retry_backoff_s
        while True:
            req.attempts += 1
            req.generated = []
            req.quant_level = level
            try:
                with span("serve.request", uid=req.uid,
                          attempt=req.attempts, level=level,
                          prompt_len=len(req.prompt),
                          max_new_tokens=req.max_new_tokens):
                    self._serve_one(req, self._params_for(level),
                                    deadline_t)
                break
            except NonFiniteLogits as e:
                nxt = _next_level(level)
                if nxt is None:
                    self._finish_failed(req, "nonfinite", str(e))
                    return
                self._h["degraded"].labels(
                    **{"from": level, "to": nxt}).inc()
                req.degraded_to = nxt
                level = nxt
            except Exception as e:  # repro: noqa RPR004 -- request isolation: failure lands on this request via _finish_failed, not the engine
                if getattr(e, "transient", False) \
                        and retries < req.max_retries:
                    retries += 1
                    self._h["retries"].inc()
                    time.sleep(backoff)
                    backoff *= 2
                    continue
                self._finish_failed(req, self._failure_reason(e), str(e))
                return
        req.error = None
        req.fallbacks = int(self._h["fallback"].value - fb0)
        req.status = ("degraded" if req.degraded_to or req.fallbacks
                      else "done")
        self.done[req.uid] = req
        self._h["n_requests"].inc()

    def _serve_one(self, req: Request, params, deadline_t: Optional[float]
                   ) -> None:
        """One serve attempt: prefill + sample, then the decode loop.
        Raises on poisoned logits, deadline overrun, or injected faults;
        appends sampled tokens to ``req.generated`` as it goes (a
        deadline failure keeps the partial output)."""
        if self.kv_pool is None:
            self._serve_attempt(req, params, deadline_t, paged=False)
            return
        # Paged path: pages for the worst case (prompt + full generation
        # budget) are held for exactly the attempt's lifetime — the
        # unconditional free keeps a failed/retried attempt from leaking
        # pool capacity (free of a never-allocated uid is a no-op).
        try:
            self._serve_attempt(req, params, deadline_t, paged=True)
        finally:
            self.kv_pool.free(req.uid)

    def _prefill_request(self, params, uid: int, prompt: jax.Array,
                         temperature: jax.Array, n_tokens: int) -> Step:
        """Prefill one ``(1, L)`` prompt on the engine's compiled step; on
        the paged path first bind pages for ``n_tokens`` tokens under
        ``uid`` (the caller frees them)."""
        if self.kv_pool is None:
            return self._prefill(params, prompt, self.key, temperature,
                                 self._table)
        page_ids = self.kv_pool.alloc(uid, n_tokens)
        cache0 = self._kvc.model_assign_sequence(self.kv_cache, 0, page_ids)
        return self._prefill_paged(params, prompt, cache0, self.key,
                                   temperature, self._table)

    def _decode_after(self, params, step: Step,
                      temperature: jax.Array) -> Step:
        """Dispatch the decode step that takes ``step``'s token, from
        ``step``'s device outputs alone."""
        return self._decode(params, step.token, step.cache, step.pos,
                            step.key, temperature, self._table)

    def teacher_forced_logits(self, prompt: np.ndarray,
                              tokens) -> np.ndarray:
        """Logits of the last prompt position, then of one decode step
        per forced token, on the engine's own compiled steps and cache.

        Returns ``(1 + len(tokens), vocab)`` fp32 (codebook 0 for
        multi-codebook configs).  Feeding every path the same tokens
        compares logits step by step, where greedy outputs of random
        weights part at the first near-tie.
        """
        params = self._params_for(self.base_level)
        uid = -1 - len(self.done)       # never a request uid
        greedy = jnp.float32(0)
        rows = []

        def keep(step):
            row = step.logits[0, -1]
            if self.cfg.n_codebooks > 1:
                row = row[0]
            rows.append(np.asarray(row[:self.cfg.vocab_size], np.float32))

        try:
            step = self._prefill_request(
                params, uid, jnp.asarray(prompt, jnp.int32)[None, :],
                greedy, len(prompt) + len(tokens) + 1)
            keep(step)
            for tok in tokens:
                step = self._decode_after(
                    params, step._replace(token=jnp.full((1, 1), tok,
                                                         jnp.int32)),
                    greedy)
                keep(step)
        finally:
            if self.kv_pool is not None:
                self.kv_pool.free(uid)
        return np.stack(rows)

    def _serve_attempt(self, req: Request, params,
                       deadline_t: Optional[float], *, paged: bool) -> None:
        """Prefill, then ``max_new_tokens - 1`` decode steps, each
        dispatched from the previous step's device outputs before the
        host reads that step's token: the device runs step n + 1 while
        the host reads, checks and appends token n."""
        h = self._h
        ledger = get_ledger()
        plan = active_fault_plan()
        uid = req.uid
        t_att = t_last = time.perf_counter()

        def take(step: Step) -> None:
            nonlocal t_last
            with span("serve.sample", uid=uid):
                tok = self._sample(step, req.temperature)
            now = time.perf_counter()
            if req.generated:
                h["tpot"].observe(now - t_last)
                h["decode_s"].inc(now - t_last)
            else:
                h["ttft"].observe(now - t_att)
                h["prefill_s"].inc(now - t_att)
            t_last = now
            h["tokens"].inc()
            req.generated.append(tok)

        with span("serve.prefill", uid=uid, length=len(req.prompt),
                  paged=paged), ledger.step("prefill"):
            with span("serve.input", uid=uid):
                prompt, temperature = jax.device_put((
                    np.asarray(req.prompt, np.int32)[None, :],
                    np.float32(req.temperature)))
                h["put_prompt"].inc()
            with span("serve.step", uid=uid):
                step = self._prefill_request(
                    params, uid, prompt, temperature,
                    len(req.prompt) + req.max_new_tokens)
        with span("serve.decode", uid=uid,
                  tokens=req.max_new_tokens - 1):
            for _ in range(req.max_new_tokens - 1):
                if deadline_t is not None \
                        and time.perf_counter() > deadline_t:
                    take(step)          # computed before the deadline
                    raise DeadlineExceeded(
                        f"decode deadline {req.deadline_s}s exceeded "
                        f"after {len(req.generated)} tokens")
                fault = plan.decode_fault() if plan is not None else None
                if fault is not None and fault.slow_s:
                    time.sleep(fault.slow_s)
                if fault is not None and fault.transient:
                    raise TransientServeError(
                        f"injected transient failure (request {uid})")
                with ledger.step("decode"):
                    with span("serve.step", uid=uid):
                        nxt = self._decode_after(params, step, temperature)
                    if fault is not None and fault.nan:
                        nxt = nxt._replace(finite=np.False_)
                    take(step)
                step = nxt
            take(step)

    def metrics_snapshot(self) -> Dict[str, dict]:
        """JSON-ready view of everything observed: the metrics registry
        plus the GEMM ledger's per-step aggregates (record list elided —
        ``get_ledger().snapshot()`` has the full dump)."""
        led = get_ledger()
        return {
            "metrics": get_metrics().snapshot(),
            "gemm_plan_sources": dict(self.gemm_plan_sources),
            "ledger": {"enabled": led.enabled,
                       "aggregate": led.aggregate(),
                       "steps": led.steps_summary()},
        }

    def metrics_report(self) -> str:
        """Human-readable serve report: metric lines (TTFT/TPOT
        histograms, prefill/decode split, tokens/s, plan sources) plus
        one line per GEMM-ledger step label with achieved GB/s and model
        error when the ledger is enabled."""
        lines = [get_metrics().report()]
        led = get_ledger()
        steps = led.steps_summary() if led.enabled else {}
        for label, agg in sorted(steps.items()):
            line = (f"ledger.{label}: steps={agg['steps']} "
                    f"gemms={agg['gemm_calls']} "
                    f"planned={agg['planned_bytes'] / 1e6:.2f}MB")
            if "achieved_gbps" in agg:
                line += f" achieved={agg['achieved_gbps']:.3f}GB/s"
            if "model_error" in agg:
                line += f" model_error={agg['model_error']:.3g}x"
            lines.append(line)
        return "\n".join(l for l in lines if l)

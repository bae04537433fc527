"""Observability stack: metrics math, trace round trip, GEMM ledger
agreement with the io_model, and the serve engine's end-to-end report.

The ledger tests pin the PR's acceptance bar: the planned bytes the
dispatch hook records must equal the io_model expressions the benchmarks
gate on — exactly, not approximately — for the three CI-gated workloads
(fused bias+gelu, one-pass GLU, w8a8).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gemm import ca_expert_matmul, ca_glu_matmul, ca_matmul
from repro.core.io_model import (epilogue_q_elements, io_volume_bytes,
                                 io_volume_elements,
                                 io_volume_elements_program)
from repro.obs import (enable_ledger, get_ledger, get_metrics, read_trace,
                       span, tracing_enabled)
from repro.obs import trace as trace_mod
from repro.obs.metrics import Histogram
from repro.obs.trace import disable_tracing, enable_tracing
from repro.tuning import get_registry


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_counter_inc_labels_and_negative():
    c = get_metrics().counter("t.requests", "test counter")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    # parent value sums the label children; children stay separate.
    assert c.value == 6.5
    assert c.labels(kind="a").value == 2
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_add_none_until_written():
    g = get_metrics().gauge("t.level", "test gauge")
    assert g.value is None
    g.set(4.0)
    g.add(-1.5)
    assert g.value == 2.5


def test_registry_kind_mismatch_raises():
    reg = get_metrics()
    reg.counter("t.same_name", "first as counter")
    with pytest.raises(TypeError):
        reg.histogram("t.same_name", "now as histogram")


def test_histogram_bucket_bounds_and_index():
    h = Histogram("t.h", "bucket math")
    # Bucket i holds (base*factor^(i-1), base*factor^i]: an exact bound
    # lands in its own bucket, epsilon above lands in the next.
    for i in (0, 3, 10):
        upper = h.bucket_upper(i)
        assert upper == h.base * h.factor ** i
        assert h._index(upper) == i
        assert h._index(upper * 1.01) == i + 1
    assert h._index(-0.5) == -1      # <=0 values must not crash
    h.observe(-0.5)
    assert h.count == 1 and h.snapshot()["min"] == -0.5


def test_histogram_stats_and_percentiles():
    h = Histogram("t.lat", "latencies")
    vals = [0.001, 0.002, 0.004, 0.008, 0.1]
    for v in vals:
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == len(vals)
    assert snap["sum"] == pytest.approx(sum(vals))
    assert snap["min"] == min(vals) and snap["max"] == max(vals)
    assert snap["mean"] == pytest.approx(np.mean(vals))
    # percentile returns the holding bucket's upper bound: an exact
    # over-estimate of at most one factor, clamped to the observed max.
    p50 = h.percentile(50)
    assert np.median(vals) <= p50 <= np.median(vals) * h.factor
    assert h.percentile(100) == max(vals)
    assert h.percentile(0) <= min(vals) * h.factor
    empty = Histogram("t.empty", "")
    assert empty.percentile(50) is None


def test_metrics_snapshot_and_report():
    reg = get_metrics()
    reg.counter("t.a", "").inc(3)
    reg.histogram("t.b", "").observe(0.5)
    snap = reg.snapshot()
    assert snap["t.a"] == {"type": "counter", "value": 3}
    assert snap["t.b"]["count"] == 1
    rep = reg.report()
    assert "t.a: 3" in rep and "t.b: count=1" in rep


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------

def test_span_is_shared_noop_when_disabled():
    assert not tracing_enabled()
    s1, s2 = span("a"), span("b", attr=1)
    assert s1 is s2 is trace_mod._NOOP
    with s1:                           # and it is a working context manager
        pass


def test_trace_roundtrip_and_nesting(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    enable_tracing(path)
    assert tracing_enabled()
    with span("outer", phase="test"):
        with span("inner", i=0):
            pass
    disable_tracing()
    assert not tracing_enabled()

    events = read_trace(path)
    by_name = {e["name"]: e for e in events}
    assert set(by_name) == {"outer", "inner"}
    for e in events:
        assert e["cat"] == "repro"
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
    inner, outer = by_name["inner"], by_name["outer"]
    assert inner["ph"] == outer["ph"] == "X"
    assert outer["args"] == {"phase": "test"}
    # Nesting is interval containment on one tid (how Perfetto rebuilds
    # the flame graph from "X" events).
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    # The array-format file is also one valid JSON document.
    import json
    text = open(path).read().rstrip().rstrip(",")
    assert len(json.loads(text + "\n]")) == len(events)


@pytest.mark.parametrize("write", ["flush", "disable", "exit"])
def test_spans_are_held_until_written(tmp_path, write):
    """Spans stay in memory: nothing reaches the file before flush(),
    and every span does after flush(), disable_tracing() or the
    process's exit."""
    path = tmp_path / "trace.jsonl"
    if write == "exit":
        import subprocess
        import sys

        code = ("from repro.obs import enable_tracing, span\n"
                f"enable_tracing({str(path)!r})\n"
                "for i in range(3):\n"
                "    with span('tick', i=i):\n"
                "        pass\n")
        subprocess.run([sys.executable, "-c", code], check=True)
    else:
        enable_tracing(str(path))
        for i in range(3):
            with span("tick", i=i):
                pass
        assert read_trace(str(path)) == []
        (trace_mod.flush if write == "flush" else disable_tracing)()
    events = read_trace(str(path))
    assert [e["args"]["i"] for e in events] == [0, 1, 2]


def test_concurrent_spans_and_flushes_lose_nothing(tmp_path, monkeypatch):
    """Threads record while others flush, with the buffer limit low so
    that recording starts writer threads too: every span reaches the
    file once."""
    import sys
    import threading

    monkeypatch.setattr(trace_mod, "MAX_BUFFERED", 50)
    path = str(tmp_path / "trace.jsonl")
    enable_tracing(path)
    n_threads, n_spans = 8, 400
    stop = threading.Event()

    def record(t):
        for i in range(n_spans):
            with span("tick", t=t, i=i):
                pass

    def flusher():
        while not stop.is_set():
            trace_mod.flush()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=record, args=(t,))
                   for t in range(n_threads)]
        flushers = [threading.Thread(target=flusher) for _ in range(2)]
        for th in workers + flushers:
            th.start()
        for th in workers:
            th.join(timeout=60)
        stop.set()
        for th in flushers:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in workers + flushers)
    finally:
        sys.setswitchinterval(interval)
    disable_tracing()
    got = sorted((e["args"]["t"], e["args"]["i"]) for e in read_trace(path))
    assert got == [(t, i) for t in range(n_threads) for i in range(n_spans)]


def test_a_span_past_the_limit_does_not_wait_for_the_write(tmp_path,
                                                            monkeypatch):
    """Crossing MAX_BUFFERED hands the write to another thread: the span
    that crossed it returns while that write is still blocked."""
    import threading
    import time

    monkeypatch.setattr(trace_mod, "MAX_BUFFERED", 4)
    path = str(tmp_path / "trace.jsonl")
    enable_tracing(path)
    tracer = trace_mod._get_tracer()
    release, writers = threading.Event(), []

    class BlockedFile:
        def __init__(self, f):
            self.f, self.closed = f, False

        def write(self, text):
            writers.append(threading.current_thread())
            assert release.wait(30)
            return self.f.write(text)

        def flush(self):
            self.f.flush()

        def close(self):
            self.closed = True
            self.f.close()

    tracer._f = BlockedFile(tracer._f)
    for i in range(12):
        with span("tick", i=i):
            pass
    deadline = time.monotonic() + 30
    while not writers and time.monotonic() < deadline:
        time.sleep(0.01)
    assert writers and threading.current_thread() not in writers
    # Every span above returned with the writer still blocked.
    assert not release.is_set()
    release.set()
    disable_tracing()
    assert [e["args"]["i"] for e in read_trace(path)] == list(range(12))


def test_kernel_scope_names_ops_and_nests():
    @trace_mod.kernel_scope("outer_k")
    def f(x, depth):
        return f(x * 2, depth - 1) if depth else x + 1

    text = jax.jit(lambda x: f(x, 1)).lower(
        jnp.ones(4)).as_text(debug_info=True)
    assert "outer_k/outer_k/add" in text
    assert "outer_k/mul" in text
    # The name stack is restored after each call.
    assert "outer_k/outer_k/outer_k" not in text


# ---------------------------------------------------------------------------
# GEMM ledger vs io_model (the CI-gated bench workloads, xla mode)
# ---------------------------------------------------------------------------

def test_ledger_disabled_is_noop(rng):
    led = get_ledger()
    assert not led.enabled
    assert led.record_gemm(8, 8, 8, jnp.float32, tag="none") is None
    ca_matmul(jnp.asarray(rng.randn(8, 16), jnp.float32),
              jnp.asarray(rng.randn(16, 8), jnp.float32))
    assert led.records == []
    assert get_metrics().snapshot() == {}


def test_ledger_fused_bytes_match_io_model(rng):
    from repro.kernels.epilogue import Epilogue
    from repro.kernels.program import program_cost

    led = enable_ledger()
    m, n, k = 37, 1024, 1024          # the fused-epilogue CI gate shape
    x = jnp.asarray(rng.randn(m, k), jnp.float32)
    w = jnp.asarray(rng.randn(k, n), jnp.float32)
    b = jnp.asarray(rng.randn(n), jnp.float32)
    ca_matmul(x, w, epilogue=Epilogue(bias=b, activation="gelu"))
    (rec,) = led.records
    assert rec.tag == "bias+gelu" and rec.dtype == "float32"
    assert rec.config_source in ("cache", "autotune", "analytic")
    tile = get_registry().resolve(m, n, k, dtype=jnp.float32,
                                  epilogue=rec.tag)
    cost = program_cost(rec.tag)
    want = (io_volume_elements(m, n, k, min(tile.bm, m), min(tile.bn, n))
            + epilogue_q_elements(m, n, cost.stream_mn, cost.has_bias,
                                  fused=True)) * 4
    assert rec.planned_bytes == want
    assert rec.planned_flops == 2.0 * m * n * k
    assert rec.planned_s > 0
    src = rec.config_source
    snap = get_metrics().snapshot()["gemm.ledger_records_total"]
    assert snap["labels"] == {f"source={src}": 1}


def test_ledger_glu_bytes_match_io_model(rng):
    led = enable_ledger()
    m, n, k = 512, 4096, 1024          # the one-pass GLU CI gate shape
    x = jnp.asarray(rng.randn(m, k), jnp.float32)
    wg = jnp.asarray(rng.randn(k, n), jnp.float32)
    wu = jnp.asarray(rng.randn(k, n), jnp.float32)
    ca_glu_matmul(x, wg, wu)
    (rec,) = led.records
    assert rec.tag == "glu.silu(none|none)"
    tile = get_registry().resolve(m, n, k, dtype=jnp.float32,
                                  epilogue=rec.tag)
    want = io_volume_elements_program(
        m, n, k, min(tile.bm, m), min(tile.bn, n), n_b=2) * 4
    assert rec.planned_bytes == want
    assert rec.planned_flops == 2.0 * m * n * k * 2   # two branches


def test_ledger_w8a8_bytes_match_io_model(rng):
    from repro.quant import quantize_tensor

    led = enable_ledger()
    m, n, k = 37, 1024, 1024           # the w8a8 CI gate shape
    qw = quantize_tensor(
        jnp.asarray(rng.randn(k, n), jnp.float32).astype(jnp.bfloat16))
    qw = dataclasses.replace(qw, act_scale=jnp.float32(0.5))
    xb = jnp.asarray(rng.randn(m, k), jnp.float32).astype(jnp.bfloat16)
    ca_matmul(xb, qw)
    (rec,) = led.records
    assert rec.tag == "dqab" and rec.dtype == "int8w_int8a"
    tile = get_registry().resolve(m, n, k, dtype=jnp.bfloat16,
                                  epilogue=rec.tag, dtype_b=jnp.int8,
                                  dtype_a=jnp.int8)
    want = io_volume_bytes(m, n, k, min(tile.bm, m), min(tile.bn, n),
                           a_itemsize=1, b_itemsize=1, out_itemsize=2) \
        + 4.0 * epilogue_q_elements(m, n, scale_b_elements=n,
                                    scale_a_elements=1)
    assert rec.planned_bytes == want
    # w8a8 plans its roofline at the MXU's int8 rate: strictly less
    # compute time than the identical bf16-rate plan would give.
    assert rec.planned_s <= max(
        rec.planned_flops / led.hw.peak_flops(jnp.bfloat16),
        rec.planned_bytes / led.hw.hbm_bandwidth)


def test_ledger_expert_loop_folds_calls(rng):
    led = enable_ledger()
    xe = jnp.asarray(rng.randn(2, 4, 8, 16), jnp.float32)
    we = jnp.asarray(rng.randn(4, 16, 32), jnp.float32)
    ca_expert_matmul(xe, we)
    (rec,) = led.records
    assert rec.calls == 4 and rec.m == 2 * 8      # per-expert token slab


def test_ledger_step_replay_and_rates(rng):
    led = enable_ledger()
    x = jnp.asarray(rng.randn(16, 32), jnp.float32)
    w = jnp.asarray(rng.randn(32, 16), jnp.float32)
    with led.step("s"):
        ca_matmul(x, w)
    with led.step("s"):                # compiled-cache-hit step: records
        pass                           # nothing, replays the traced program
    agg = led.steps_summary()["s"]
    assert agg["steps"] == 2 and agg["gemm_calls"] == 2
    assert agg["planned_bytes"] == 2 * led.records[0].planned_bytes
    assert agg["achieved_gbps"] > 0 and agg["model_error"] > 0


# ---------------------------------------------------------------------------
# serve engine end to end
# ---------------------------------------------------------------------------

def test_serve_engine_metrics_e2e():
    from collections import Counter as TallyCounter

    from repro.configs import get_reduced
    from repro.models import model as M
    from repro.serve.engine import Request, ServeEngine

    enable_ledger()
    cfg = get_reduced("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(params, cfg, batch_size=1, max_len=24)
    r = np.random.RandomState(0)
    new_tokens = [4, 3]
    for uid, n_new in enumerate(new_tokens):
        eng.submit(Request(uid=uid,
                           prompt=r.randint(0, cfg.vocab_size, 6),
                           max_new_tokens=n_new))
    eng.run()

    snap = eng.metrics_snapshot()
    mets = snap["metrics"]
    assert mets["serve.ttft_seconds"]["count"] == len(new_tokens)
    assert mets["serve.ttft_seconds"]["min"] > 0
    assert mets["serve.tpot_seconds"]["count"] == sum(
        n - 1 for n in new_tokens)
    assert mets["serve.queue_wait_seconds"]["count"] == len(new_tokens)
    assert mets["serve.tokens_generated_total"]["value"] == sum(new_tokens)
    assert mets["serve.requests_total"]["value"] == len(new_tokens)
    assert mets["serve.tokens_per_second"]["value"] > 0
    assert mets["serve.warmup_seconds"]["value"] > 0
    # Plan-source counter must tally exactly the warmup's plan map.
    want_sources = TallyCounter(eng.gemm_plan_sources.values())
    got = mets["serve.gemm_plan_total"]["labels"]
    assert got == {f"source={s}": c for s, c in want_sources.items()}
    # Ledger: one prefill step per request, one decode step per non-first
    # token, each with achieved-vs-planned rates.
    steps = snap["ledger"]["steps"]
    assert steps["prefill"]["steps"] == len(new_tokens)
    assert steps["decode"]["steps"] == sum(n - 1 for n in new_tokens)
    for agg in steps.values():
        assert agg["gemm_calls"] > 0 and agg["planned_bytes"] > 0
        assert agg["achieved_gbps"] > 0 and agg["model_error"] > 0

    report = eng.metrics_report()
    for needle in ("serve.ttft_seconds", "serve.tpot_seconds",
                   "serve.tokens_per_second", "serve.gemm_plan_total",
                   "ledger.prefill", "ledger.decode", "model_error"):
        assert needle in report, needle


def _serve_small(new_tokens, *, paged=False, check_finite=True):
    from repro.configs import get_reduced
    from repro.models import model as M
    from repro.serve.engine import Request, ServeEngine

    cfg = get_reduced("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(params, cfg, batch_size=1, max_len=24,
                      warmup_gemms=False, paged_kv=paged,
                      check_finite=check_finite)
    r = np.random.RandomState(0)
    for uid, n_new in enumerate(new_tokens):
        eng.submit(Request(uid=uid, prompt=r.randint(0, cfg.vocab_size, 6),
                           max_new_tokens=n_new))
    return eng.run()


PHASES = ("serve.step", "serve.sample")


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
def test_serve_spans_one_per_phase_per_token(tmp_path, paged):
    """The prefill span holds the prompt's put and the prefill dispatch;
    the decode span holds each decode dispatch, each followed by the
    read of the token before it, then the last token's read."""
    path = str(tmp_path / "trace.jsonl")
    enable_tracing(path)
    new_tokens = [4, 3]
    done = _serve_small(new_tokens, paged=paged)
    disable_tracing()
    assert all(r.status == "done" for r in done.values())
    events = read_trace(path)
    for uid, n_new in enumerate(new_tokens):
        mine = [e for e in events if e.get("args", {}).get("uid") == uid]
        (prefill,) = [e for e in mine if e["name"] == "serve.prefill"]
        (decode,) = [e for e in mine if e["name"] == "serve.decode"]

        def inside(e, outer):
            return (e["tid"] == outer["tid"] and outer["ts"] <= e["ts"]
                    and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"])

        (put,) = [e for e in mine if e["name"] == "serve.input"]
        assert inside(put, prefill)
        assert not [e for e in mine if e["name"] == "serve.finite"]
        steps = [e for e in mine if e["name"] == "serve.step"]
        assert len(steps) == n_new
        assert sum(inside(e, prefill) for e in steps) == 1
        assert sum(inside(e, decode) for e in steps) == n_new - 1
        samples = [e for e in mine if e["name"] == "serve.sample"]
        assert len(samples) == n_new
        assert all(inside(e, decode) for e in samples)
        # Within the decode span each dispatch precedes the read of the
        # token before it.
        in_decode = sorted((e for e in mine if e["name"] in PHASES
                            and inside(e, decode)), key=lambda e: e["ts"])
        assert [e["name"] for e in in_decode] == \
            list(PHASES) * (n_new - 1) + ["serve.sample"]


@pytest.mark.parametrize("check_finite", [True, False],
                         ids=["checked", "unchecked"])
def test_host_sync_and_put_counters(check_finite):
    new_tokens = [4, 3]
    _serve_small(new_tokens, check_finite=check_finite)
    mets = get_metrics().snapshot()
    n_req, n_tok = len(new_tokens), sum(new_tokens)
    syncs = mets["serve.host_syncs_total"]["labels"]
    puts = mets["serve.host_puts_total"]["labels"]
    # Every token (prefill's and each decode step's) is read once, with
    # its finite flag, whether or not the flag is acted on.
    assert syncs == {"at=sample": n_tok}
    assert mets["serve.host_syncs_total"]["value"] == n_tok
    # Each request puts its prompt; decode steps put nothing.
    assert puts == {"what=prompt": n_req}

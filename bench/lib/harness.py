"""One run of one cell: set-up, the measured window, the check.

The system under test is ``ServeEngine`` (``serve/engine.py``) at its
defaults, on the model's jitted prefill and decode steps through the
slab KV cache.  ``ServeEngine.run`` blocks until its queue is empty, so a
serving thread calls it whenever requests are queued, while the main
thread submits each request when it falls due, also while the engine is
serving.

Times are the harness's own host-clock readings: each request records
``time.perf_counter()`` when it is due and whenever the engine appends a
token to it (:class:`TimedRequest`), so the first token and completion
are read where the engine hands them over, not from the program's spans.
The program's spans (``serve.request`` and friends) are written to the
run's output directory for the per-layer readers.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import hashlib
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import threading
import time
import types
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from . import traffic as traffic_mod
from .trace import WINDOW

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
OUT = BENCH / "out"


# ---------------------------------------------------------------------------
# Requests that time themselves
# ---------------------------------------------------------------------------

class _TimedTokens(list):
    """The engine's ``req.generated`` list, stamping each append."""

    def __init__(self, owner: "TimedRequest"):
        super().__init__()
        self._owner = owner

    def append(self, tok):
        super().append(tok)
        owner = self._owner
        owner.token_times.append(time.perf_counter())
        if owner.on_token is not None:
            owner.on_token(owner)


def timed_request_class():
    """``Request`` of the program, with its tokens timed on arrival."""
    from repro.serve.engine import Request

    class TimedRequest(Request):
        due: float = 0.0
        submitted: float = 0.0
        # Called on the serving thread after each token is appended.
        on_token: Optional[Callable] = None

        @property
        def generated(self):
            return self.__dict__.get("_generated")

        @generated.setter
        def generated(self, value):
            # The engine assigns a fresh list for every attempt.
            self.token_times = []
            self.__dict__["_generated"] = (
                None if value is None else _TimedTokens(self))
            if value:
                self.__dict__["_generated"].extend(value)

    return TimedRequest


# ---------------------------------------------------------------------------
# The run's record, read by the metric files
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ReqRecord:
    uid: int
    prompt_len: int
    max_new_tokens: int
    due: float                     # seconds from the window's start
    submitted: float
    token_times: List[float]       # seconds from the window's start
    status: str
    tokens_at_close: int

    @property
    def first(self) -> Optional[float]:
        return self.token_times[0] if self.token_times else None

    @property
    def completed(self) -> bool:
        return self.status == "done" and \
            len(self.token_times) == self.max_new_tokens


@dataclasses.dataclass
class RunRecord:
    """Everything a metric file may read.  Times are seconds from the
    window's start on the host clock."""
    cell: str
    block: types.ModuleType        # bench/reference/<block>.py
    spec: Any                      # the block's spec of the config
    loop: str
    seconds: float
    requests: List[ReqRecord]
    spans: List[dict]              # program spans, ts/dur in seconds
    peaks: dict
    closed_at: float = 0.0         # when the drain after the window ended
    trace: Optional[dict] = None   # summary of the traced window
    trace_window: Optional[tuple] = None   # (start, end) seconds
    profile_started: Optional[float] = None   # when start_trace was called

    def steps_in(self, lo: float, hi: float):
        """(kind, size) of every model step whose token arrived in
        [lo, hi): ("prefill", prompt_len) or ("decode", context)."""
        out = []
        for r in self.requests:
            for i, t in enumerate(r.token_times):
                if lo <= t < hi:
                    out.append(("prefill", r.prompt_len) if i == 0
                               else ("decode", r.prompt_len + i))
        return out


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


@functools.lru_cache(maxsize=None)
def _load_block(path: str) -> types.ModuleType:
    # One module name per path; the module is registered before it runs,
    # as a dataclass in it needs.
    name = "bench_block_" + hashlib.sha1(path.encode()).hexdigest()[:12]
    found = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(found)
    sys.modules[name] = mod
    found.loader.exec_module(mod)
    return mod


def load_config(bench_dir: pathlib.Path, name: str
                ) -> Tuple[dict, types.ModuleType, Any]:
    """The file ``configs/<name>.json``, the block module that its
    ``"block"`` key names (``reference/<block>.py``, loaded once per
    path), and that block's spec of the file."""
    path = bench_dir / "configs" / f"{name}.json"
    raw = json.loads(path.read_text())
    if "block" not in raw:
        raise ValueError(f"{path}: no \"block\" key; it names the module "
                         "reference/<block>.py that computes the config")
    block = _load_block(str(bench_dir / "reference" / f"{raw['block']}.py"))
    return raw, block, block.load_spec(raw, name)


def setup_compile_cache(root: pathlib.Path = ROOT) -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<checkout>/.jax_cache``, a fixed path."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(root / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileEvents:
    """Counts compilations (backend compiles and cache reads) by time."""

    def __init__(self):
        import jax

        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        # Recorded once per program compiled or read from the cache.
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.perf_counter())

    def between(self, lo: float, hi: float) -> int:
        return sum(1 for t in self.times if lo <= t < hi)


@dataclasses.dataclass
class Options:
    """How one run is made; the defaults are the benchmark's."""
    trace: bool = False
    out_dir: pathlib.Path = OUT
    # The profile covers ``trace_len_s`` seconds at the window's end,
    # started ``trace_lead_s`` earlier (what starting the profiler
    # takes).  Profiling slows the service, and at 0.8 of the knee the
    # queue it builds lasts for the rest of the window, so the requests
    # before it are read unprofiled.
    trace_len_s: float = 5.0
    trace_lead_s: float = 1.5


def run_cell(cell: dict, seed: int, seconds: float, t_start: float,
             opts: Options, bench_dir: pathlib.Path = BENCH,
             mix: Optional[traffic_mod.Mix] = None) -> dict:
    """One run of ``cell``; returns the result line's fields and the
    run's record under ``"record"``.  ``mix`` replaces the cell's
    traffic file (the knee sweep's other rates)."""
    import jax

    from repro.obs import trace as spans_mod
    from repro.serve.engine import ServeEngine

    raw, block, spec = load_config(bench_dir, cell["config"])
    mix = mix or traffic_mod.load_mix(
        bench_dir / "traffic" / f"{cell['traffic']}.json")
    peaks = json.loads((bench_dir / "peaks.json").read_text())
    cfg = block.program_config(spec)
    out_dir = pathlib.Path(opts.out_dir) / f"{cell['name']}.{seed}"
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)

    # -- set-up: weights, engine, one warm request per prompt length ------
    params = block.program_weights(spec, seed, cfg)
    engine = ServeEngine(params, cfg, batch_size=mix.batch_size,
                         max_len=mix.max_len, seed=0)
    TimedRequest = timed_request_class()
    warm_rng = np.random.default_rng(0)
    for i, L in enumerate(mix.prompt_lengths):
        engine.submit(TimedRequest(
            uid=-1 - i, prompt=warm_rng.integers(0, spec.vocab, L,
                                                 dtype=np.int32),
            max_new_tokens=2))
    warm = engine.run()
    bad = [r.status for r in warm.values() if r.status != "done"]
    if bad:
        raise RuntimeError(f"warm-up requests did not finish: {bad}")
    engine.done.clear()
    jax.effects_barrier()

    compiles = CompileEvents()
    span_path = out_dir / "spans.jsonl"
    span_t0 = time.perf_counter()   # the span clock's zero, within ~0.1 ms
    spans_mod.enable_tracing(str(span_path))
    plan = traffic_mod.generate(mix, seed, spec.vocab)
    plan_lock = threading.Lock()

    def next_planned():
        with plan_lock:
            return next(plan)

    # -- the serving thread --------------------------------------------
    wake = threading.Event()
    stop = threading.Event()
    closing = threading.Event()
    busy = threading.Event()
    errors: List[BaseException] = []

    def serve():
        try:
            while not stop.is_set():
                wake.wait(0.05)
                wake.clear()
                while engine.queue and not stop.is_set():
                    busy.set()
                    engine.run()
                    busy.clear()
        except BaseException as e:  # repro: noqa RPR004 -- re-raised on the main thread
            errors.append(e)
            busy.clear()

    server = threading.Thread(target=serve, name="bench-serve", daemon=True)
    server.start()

    submitted: List = []
    lateness: List[float] = []
    lock = threading.Lock()
    t0 = time.perf_counter()
    t_end = t0 + seconds

    def on_token(req):
        # On the serving thread, inside engine.run(): the only place the
        # queue may be emptied without racing the engine's own pops.
        if closing.is_set():
            engine.queue.clear()
        elif mix.loop == "closed" and len(req.generated) == \
                req.max_new_tokens:
            now = time.perf_counter()
            if now < t_end:
                submit(next_planned(), now)
            else:
                engine.queue.clear()

    def submit(planned, due: float):
        r = TimedRequest(uid=planned.index, prompt=planned.prompt,
                         max_new_tokens=planned.max_new_tokens)
        r.due, r.on_token = due, on_token
        with lock:
            submitted.append(r)
        r.submitted = time.perf_counter()
        engine.submit(r)
        lateness.append(r.submitted - due)
        wake.set()
        return r

    setup_s = t0 - t_start
    trace_dir = out_dir / "profile"
    profiled = {}

    def profile():
        # Its own thread, so that starting the profiler (about a second)
        # does not hold up the generator.
        time.sleep(max(t_end - opts.trace_len_s - opts.trace_lead_s
                       - time.perf_counter(), 0.0))
        started = time.perf_counter()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        with jax.profiler.TraceAnnotation(WINDOW):
            lo = time.perf_counter()
            time.sleep(max(min(lo + opts.trace_len_s, t_end) - lo, 0.0))
            hi = time.perf_counter()
        jax.profiler.stop_trace()
        profiled.update(started=started - t0, lo=lo - t0, hi=hi - t0)

    profiler = threading.Thread(target=profile, name="bench-profile",
                                daemon=True)
    if opts.trace:
        profiler.start()

    def sleep_until(t: float):
        time.sleep(max(t - time.perf_counter(), 0.0))

    # -- the window ----------------------------------------------------
    if mix.loop == "open":
        due = t0
        for planned in plan:
            due += planned.gap_s
            if due >= t_end:
                break
            sleep_until(due)
            submit(planned, due)
    else:
        for _ in range(mix.clients):
            submit(next_planned(), t0)
    sleep_until(t_end)
    with lock:
        at_close = {id(r): len(r.generated or ()) for r in submitted}
    compiles_in_window = compiles.between(t0, t_end)

    # -- after the window ------------------------------------------------
    # An open loop follows the requests that fell due to their first
    # token, up to the drain limit; a closed loop stops at the end of the
    # request in flight.  Either way the engine then empties its queue.
    drain_end = t_end + mix.drain_s
    if mix.loop == "open":
        while time.perf_counter() < drain_end and not errors:
            with lock:
                waiting = any(not r.token_times and r.status == "queued"
                              or r.status == "running" for r in submitted)
            if not waiting:
                break
            time.sleep(0.01)
    closing.set()
    while (busy.is_set() or engine.queue) and not errors:
        time.sleep(0.01)
    closed_at = time.perf_counter() - t0
    stop.set()
    wake.set()
    server.join()
    spans_mod.disable_tracing()
    if errors:
        raise errors[0]

    # -- the record ----------------------------------------------------
    stats = jax.devices()[0].memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")
    requests = [ReqRecord(
        uid=r.uid, prompt_len=len(r.prompt), max_new_tokens=r.max_new_tokens,
        due=r.due - t0, submitted=r.submitted - t0,
        token_times=[t - t0 for t in r.token_times], status=r.status,
        tokens_at_close=at_close[id(r)]) for r in submitted]
    spans = []
    for ev in spans_mod.read_trace(str(span_path)):
        if ev.get("ph") == "X":
            ev = dict(ev)
            ev["ts"] = ev["ts"] * 1e-6 + span_t0 - t0
            ev["dur"] = ev["dur"] * 1e-6
            spans.append(ev)
    record = RunRecord(cell=cell["name"], block=block, spec=spec,
                       loop=mix.loop, seconds=seconds, requests=requests,
                       spans=spans, closed_at=closed_at,
                       peaks=peaks[jax.devices()[0].device_kind]
                       if jax.devices()[0].device_kind in peaks else {})
    if opts.trace:
        profiler.join()
        from . import trace as trace_mod

        record.trace = trace_mod.summary(
            trace_mod.load(trace_mod.find_xplane(str(trace_dir))))
        record.trace_window = (profiled["lo"], profiled["hi"])
        record.profile_started = profiled["started"]

    served = {r.uid: (np.asarray(r.prompt), list(r.generated or ()))
              for r in submitted if r.status == "done"
              and len(r.generated) == r.max_new_tokens}
    failed = sum(1 for r in submitted if r.status in ("failed", "degraded"))
    del engine, params, submitted, warm
    gc.collect()

    out = {
        "setup_s": setup_s,
        "compiles_in_window": compiles_in_window,
        "lateness": lateness,
        "peak_bytes": peak_bytes,
        "record": record,
        "config": raw,
        "served": served,
        "failed": failed,
        "out_dir": out_dir,
        "mix": mix,
    }
    return out

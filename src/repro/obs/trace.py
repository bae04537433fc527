"""Tracing spans: Chrome-trace-event / Perfetto-compatible JSONL.

``span("name", **attrs)`` wraps any region of host code; when tracing is
enabled each completed span becomes one complete ("ph": "X") trace
event.  Events are kept in memory and written out, one JSON line each,
by :func:`flush`, by :func:`disable_tracing` and at process exit, so
the hot path does no formatting and no file I/O.  Past
:data:`MAX_BUFFERED` held events a daemon thread writes them, and the
span that crossed the limit returns at once.  The file is in the Trace Event *array*
format, whose closing bracket is optional by spec — so it is
line-appendable and still a valid JSON-array trace that loads directly
in Perfetto / chrome://tracing.

Enable with ``REPRO_TRACE=<path>`` in the environment (``1`` means the
default ``trace.jsonl``) or programmatically via :func:`enable_tracing`.
Disabled — the default — a span is a shared no-op context manager: no
file is opened, no event object is built, no lock is taken.

When a real ``jax.profiler`` is present each enabled span also enters a
``TraceAnnotation`` of the same name, so device profiles
(``jax.profiler.trace``) carry the program's spans on the device
trace's clock; on hosts without one this degrades silently.

:func:`kernel_scope` is the compiled-program half of the same naming: it
puts a function's ops under a ``jax.named_scope``, which the device
trace reports as each op's ``tf_op`` path.
"""

from __future__ import annotations

import atexit
import collections
import functools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

_ENV_TRACE = "REPRO_TRACE"
DEFAULT_TRACE_PATH = "trace.jsonl"
# Held events past which a writer thread starts: several times what a
# minute of serving records (four spans per token, about 10k), so a run
# of that length writes once, at its end.
MAX_BUFFERED = 1 << 16


def _annotation_class():
    """``jax.profiler.TraceAnnotation`` when importable, else None."""
    try:  # deferred: obs must import without jax on the path
        from jax.profiler import TraceAnnotation
    except Exception:  # repro: noqa RPR004 -- pragma: no cover, import probe of an optional jax API
        return None
    return TraceAnnotation


class _Tracer:
    """In-memory span buffer and its JSONL file (one per process)."""

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        # (name, start_us, end_us, tid, attrs); deque appends and pops
        # are atomic, so recording takes no lock.
        self._events: collections.deque = collections.deque()
        self._writing = False       # a writer thread is on its way
        self._f = open(path, "w")
        self._f.write("[\n")          # array format; "]" optional by spec
        self._f.flush()
        self.pid = os.getpid()
        self._t0 = time.perf_counter()
        self.annotation = _annotation_class()    # resolved once

    def now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def record(self, event: tuple) -> None:
        self._events.append(event)
        if len(self._events) > MAX_BUFFERED and not self._writing:
            self._writing = True
            threading.Thread(target=self._write_behind, daemon=True,
                             name="repro-trace-writer").start()

    def _write_behind(self) -> None:
        try:
            self.flush()
        finally:
            self._writing = False

    def flush(self) -> None:
        """Write every held event, in the order they completed."""
        with self._lock:
            if self._f.closed:
                return
            # Only flush pops, under the lock, so the events held now
            # are there to pop; those recorded meanwhile wait their turn.
            pop, lines = self._events.popleft, []
            for _ in range(len(self._events)):
                name, ts, end, tid, attrs = pop()
                event = {"name": name, "ph": "X", "ts": ts,
                         "dur": end - ts, "pid": self.pid, "tid": tid,
                         "cat": "repro"}
                if attrs:
                    event["args"] = {k: _jsonable(v)
                                     for k, v in attrs.items()}
                lines.append(json.dumps(event, sort_keys=True) + ",\n")
            self._f.write("".join(lines))
            self._f.flush()

    def close(self) -> None:
        self.flush()
        with self._lock:
            self._f.close()


_state_lock = threading.Lock()
_tracer: Optional[_Tracer] = None
_env_checked = False


def enable_tracing(path: str = DEFAULT_TRACE_PATH) -> str:
    """Start recording trace events for ``path`` (truncates). Returns path."""
    global _tracer, _env_checked
    with _state_lock:
        if _tracer is not None:
            _tracer.close()
        _tracer = _Tracer(path)
        _env_checked = True
        return path


def disable_tracing() -> None:
    """Stop tracing; every recorded event is written and the file closed."""
    global _tracer, _env_checked
    with _state_lock:
        if _tracer is not None:
            _tracer.close()
        _tracer = None
        _env_checked = True     # an explicit disable beats the env var


def tracing_enabled() -> bool:
    return _get_tracer() is not None


def flush() -> None:
    """Write every event recorded so far (tracing stays on)."""
    t = _get_tracer()
    if t is not None:
        t.flush()


@atexit.register
def _flush_at_exit() -> None:
    t = _tracer
    if t is not None:
        t.flush()


def _get_tracer() -> Optional[_Tracer]:
    """The active tracer, honoring REPRO_TRACE on first use."""
    global _tracer, _env_checked
    if _tracer is not None:
        return _tracer
    if _env_checked:
        return None
    with _state_lock:
        if not _env_checked:
            _env_checked = True
            val = os.environ.get(_ENV_TRACE, "")
            if val and val != "0":
                path = DEFAULT_TRACE_PATH if val == "1" else val
                _tracer = _Tracer(path)
    return _tracer


class _NoopSpan:
    """Shared do-nothing span (tracing disabled)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class Span:
    """An active span: records one "X" event when it ends."""

    __slots__ = ("name", "attrs", "tracer", "_annotation", "_start_us")

    def __init__(self, name: str, tracer: _Tracer, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.tracer = tracer
        self._annotation = (tracer.annotation(name)
                            if tracer.annotation is not None else None)
        self._start_us = 0.0

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        self._start_us = self.tracer.now_us()
        return self

    def __exit__(self, *exc):
        self.tracer.record((self.name, self._start_us, self.tracer.now_us(),
                            threading.get_ident() & 0x7FFFFFFF, self.attrs))
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


def span(name: str, **attrs):
    """Context manager tracing one named region.

    Attrs become the event's ``args`` (shown in the Perfetto detail
    pane); values are JSON-encoded, non-scalars via ``str``.  Nesting is
    expressed by the containment of [ts, ts+dur] intervals on one tid —
    exactly how Chrome trace viewers reconstruct flame graphs from "X"
    events, so nothing extra is recorded per level.
    """
    tracer = _get_tracer()
    if tracer is None:
        return _NOOP
    return Span(name, tracer, attrs)


def kernel_scope(name: str):
    """Decorator: stage the function's ops under ``jax.named_scope(name)``.

    The scope only labels the compiled program (each op's name path, the
    ``tf_op`` of a device trace), so it costs nothing when the program
    runs.  A fresh scope is entered per call, so recursion nests cleanly.
    """
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            from jax import named_scope

            with named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


def read_trace(path: str) -> List[Dict[str, Any]]:
    """Parse a trace file written by this module (the validation half of
    the JSONL round trip: one event per line, array brackets and trailing
    commas tolerated exactly as the Trace Event spec allows)."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip().rstrip(",")
            if line in ("", "[", "]"):
                continue
            events.append(json.loads(line))
    return events

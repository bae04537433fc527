"""Reduction of a ``jax.profiler`` trace to the device's busy time.

The harness brackets the traced window with a host annotation named
:data:`WINDOW`; everything here is clipped to it.

- Busy time is the union of the intervals of the device's operations
  (the ``XLA Ops`` line of each ``/device:`` plane), averaged over the
  devices; the idle share is 1 minus busy over the window.
- Top operations: the total device time per operation name.
- Idle gaps: the stretches of the window in which no operation ran,
  each named by the innermost host event open at its middle on a
  thread of the serving engine (a thread that recorded a ``serve.``
  span or dispatched a jitted function in the trace); gaps shorter than
  :data:`SHORT_GAP_NS` share one name.
"""

from __future__ import annotations

import dataclasses
import glob
import lzma
import os
import pathlib
from typing import Dict, List, Sequence, Tuple

WINDOW = "bench.window"
DEVICE_LINE = "XLA Ops"
# Host lines of the serving thread: they hold the program's spans or
# dispatch its jitted functions.
SERVING_PREFIXES = ("serve.", "PjitFunction")
# Gaps shorter than this lie between the operations of one program.
SHORT_GAP_NS = 20_000
SHORT_GAP_NAME = "between ops (<20us)"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Trace:
    """What the reduction needs of one trace, in nanoseconds."""
    window: Interval
    device_ops: List[List[Tuple[float, float, str]]]   # per device
    host: Dict[str, List[Tuple[float, float, str]]]    # per host line


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: the TPU
    trace names an operation by its whole HLO instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file (or one compressed with xz)."""
    from jax.profiler import ProfileData

    raw = pathlib.Path(path).read_bytes()
    if str(path).endswith(".xz"):
        raw = lzma.decompress(raw)
    data = ProfileData.from_serialized_xspace(raw)
    devices, host, window = [], {}, None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [(e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                   for line in plane.lines if line.name == DEVICE_LINE
                   for e in line.events]
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events]
                host[f"{i}:{line.name}"] = events
                for s, t, n in events:
                    if n == WINDOW:
                        window = (s, t)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation in the trace")
    return Trace(window, devices, host)


def merge(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    """The union of ``intervals`` clipped to ``window``, sorted."""
    lo, hi = window
    clipped = sorted((max(s, lo), min(t, hi)) for s, t in intervals
                     if t > lo and s < hi)
    out: List[List[float]] = []
    for s, t in clipped:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_ns(ops, window: Interval) -> float:
    return sum(t - s for s, t in merge([(s, t) for s, t, _ in ops], window))


def gaps(ops, window: Interval) -> List[Interval]:
    """Stretches of the window with no operation on the device."""
    out, cur = [], window[0]
    for s, t in merge([(s, t) for s, t, _ in ops], window):
        if s > cur:
            out.append((cur, s))
        cur = t
    if cur < window[1]:
        out.append((cur, window[1]))
    return out


def top_ops(ops, window: Interval, n: int = 10) -> List[Tuple[str, float]]:
    """Operation names by their total device seconds in the window."""
    lo, hi = window
    total: Dict[str, float] = {}
    for s, t, name in ops:
        d = min(t, hi) - max(s, lo)
        if d > 0:
            total[name] = total.get(name, 0.0) + d
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns * 1e-9) for name, ns in ranked]


def serving_lines(trace: Trace) -> List[str]:
    return [k for k, ev in trace.host.items()
            if any(n.startswith(SERVING_PREFIXES) for _, _, n in ev)]


def name_at(trace: Trace, lines: Sequence[str], t: float) -> str:
    """The innermost host event open at ``t`` on the given lines."""
    best, best_d = "idle host", float("inf")
    for key in lines:
        for s, e, n in trace.host[key]:
            if s <= t < e and e - s < best_d:
                best, best_d = n, e - s
    return best


def named_gaps(trace: Trace) -> List[Tuple[str, float]]:
    """Every idle gap of device 0 with its name and seconds.  Gaps under
    :data:`SHORT_GAP_NS` (between the operations of one program) are
    not looked up and go under one name."""
    if not trace.device_ops:
        return []
    lines = serving_lines(trace)
    out = []
    for s, t in gaps(trace.device_ops[0], trace.window):
        name = (name_at(trace, lines, (s + t) / 2) if t - s >= SHORT_GAP_NS
                else SHORT_GAP_NAME)
        out.append((name, (t - s) * 1e-9))
    return out


def summary(trace: Trace, n: int = 10) -> dict:
    """busy_s (mean over devices), window_s, the ``n`` operations with
    the most device time, the ``n`` longest idle gaps, and idle seconds
    summed by name."""
    window_s = (trace.window[1] - trace.window[0]) * 1e-9
    busy = [busy_ns(ops, trace.window) * 1e-9 for ops in trace.device_ops]
    named = named_gaps(trace)
    by_name: Dict[str, float] = {}
    for name, sec in named:
        by_name[name] = by_name.get(name, 0.0) + sec
    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": window_s,
        "device_ops": [list(x) for x in top_ops(
            trace.device_ops[0] if trace.device_ops else [], trace.window,
            n)],
        "idle_gaps": [list(x) for x in sorted(named,
                                              key=lambda g: -g[1])[:n]],
        "idle_by_name": by_name,
    }

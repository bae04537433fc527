"""Device time under the program's own names, from a run's profile.

The serve engine jits its steps as named functions (``serve_prefill``,
``serve_decode``), so the device trace's ``XLA Modules`` line reads
``jit_serve_decode(<id>)``, and every op of that program carries a
``tf_op`` name path that starts with ``jit(serve_decode)`` and holds the
kernel family's scope: ``/gemm/``, ``/attn/`` or ``/kv_write/``.
``jax.profiler.ProfileData`` gives an event's name and times only, so
this module reads the device planes of the ``.xplane.pb`` itself, from
the protobuf wire format (no protobuf package needed).  Host events, and
the traced window, come from :mod:`bench.lib.trace`.

Every quantity is per decode step: divided by the number of
``jit_serve_decode`` executions that lie wholly in the traced window;
idle time by those of them inside the ``serve.decode`` spans it is
read from.  Only device 0 is read (the cells run on one chip).

    python3 -m bench.lib.layers <file.xplane.pb[.xz]> ...

prints the per-step split (device, each scope, unscoped, idle by phase)
as JSON.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import json
import lzma
import os
import pathlib
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace as trace_mod

DECODE_MODULE = "jit_serve_decode("
DECODE_PATH = "jit(serve_decode)/"
SCOPES = ("gemm", "attn", "kv_write")
DECODE_SPAN = "serve.decode"
PHASES = ("serve.input", "serve.step", "serve.finite", "serve.sample")
# Ops that hold other ops of the same line (a loop, a branch, a call).
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[float, float]


# ---------------------------------------------------------------------------
# The XPlane wire format
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    b = buf[i]
    if b < 0x80:
        return b, i + 1
    value, shift = b & 0x7F, 7
    while True:
        i += 1
        b = buf[i]
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i + 1
        shift += 7


def _fields(buf: bytes, start: int, end: int):
    """``(field, value)`` of one message; a length-delimited value is its
    ``(start, end)`` in ``buf``, a fixed-width one its raw bytes."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


@dataclasses.dataclass
class OpMeta:
    name: str                      # the HLO op's name, e.g. ``fusion.58``
    tf_op: Optional[str] = None    # the program's name path
    source: Optional[str] = None   # ``file:line`` that staged the op


def _stat_metadata(buf: bytes, span) -> Tuple[int, str]:
    sid, name = 0, ""
    for f, v in _fields(buf, *span):
        if f == 1:
            sid = v
        elif f == 2:
            name = _text(buf, v)
    return sid, name


def _event_metadata(buf: bytes, span, stat_names: Dict[int, str]):
    """``(id, OpMeta)``; ``tf_op`` and ``source`` are stats of the
    metadata, held as strings or as references to a stat name."""
    mid, name, display, stats = 0, "", "", {}
    for f, v in _fields(buf, *span):
        if f == 1:
            mid = v
        elif f == 2:
            name = _text(buf, v)
        elif f == 4:
            display = _text(buf, v)
        elif f == 5:
            key, value = None, None
            for sf, sv in _fields(buf, *v):
                if sf == 1:
                    key = stat_names.get(sv)
                elif sf == 5:
                    value = _text(buf, sv)
                elif sf == 7:
                    value = stat_names.get(sv)
            if key in ("tf_op", "source"):
                stats[key] = value
    return mid, OpMeta(display or name, stats.get("tf_op"),
                       stats.get("source"))


def _events(buf: bytes, span, t0_ns: int) -> List[Tuple[float, float, int]]:
    """``(start_ns, end_ns, metadata_id)`` of one line's events."""
    out = []
    for f, v in _fields(buf, *span):
        if f != 4:
            continue
        mid = offset = dur = 0
        for ef, ev in _fields(buf, *v):
            if ef == 1:
                mid = ev
            elif ef == 2:
                offset = ev
            elif ef == 3:
                dur = ev
        # Whole nanoseconds, as ``jax.profiler.ProfileData`` gives them
        # to bench.lib.trace.
        start = float(t0_ns + offset // 1000)
        out.append((start, start + dur // 1000, mid))
    return out


def read_device_planes(raw: bytes) -> List[dict]:
    """Each ``/device:`` plane of an XSpace: its name, its ops' metadata
    by id, and the events of its lines by line name."""
    planes = []
    for f, span in _fields(raw, 0, len(raw)):
        if f != 1:
            continue
        name, lines, ev_meta, stat_meta = "", [], [], []
        for pf, pv in _fields(raw, *span):
            if pf == 2:
                name = _text(raw, pv)
            elif pf == 3:
                lines.append(pv)
            elif pf == 4:
                ev_meta.append(pv)
            elif pf == 5:
                stat_meta.append(pv)
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for entry in stat_meta:      # map<int64, XStatMetadata>
            for mf, mv in _fields(raw, *entry):
                if mf == 2:
                    sid, sname = _stat_metadata(raw, mv)
                    stat_names[sid] = sname
        meta = {}
        for entry in ev_meta:        # map<int64, XEventMetadata>
            for mf, mv in _fields(raw, *entry):
                if mf == 2:
                    mid, m = _event_metadata(raw, mv, stat_names)
                    meta[mid] = m
        by_line = {}
        for lspan in lines:
            lname, t0, events = "", 0, None
            for lf, lv in _fields(raw, *lspan):
                if lf == 2:
                    lname = _text(raw, lv)
                elif lf == 3:
                    t0 = lv
            if lname in ("XLA Modules", trace_mod.DEVICE_LINE):
                events = _events(raw, lspan, t0)
                by_line.setdefault(lname, []).extend(events)
        planes.append({"name": name, "meta": meta, "lines": by_line})
    return planes


def read_raw(path: str) -> bytes:
    raw = pathlib.Path(path).read_bytes()
    return lzma.decompress(raw) if str(path).endswith(".xz") else raw


def _device_0(raw: bytes) -> Optional[dict]:
    """The first device plane that ran XLA ops."""
    for plane in read_device_planes(raw):
        if plane["lines"].get(trace_mod.DEVICE_LINE):
            return plane
    return None


def op_metadata(path: str) -> List[OpMeta]:
    """Device 0's op metadata: what the trace says the program named
    each op (``fusion.58``), and where it was staged."""
    plane = _device_0(read_raw(path))
    return [] if plane is None else list(plane["meta"].values())


# ---------------------------------------------------------------------------
# One profile, reduced by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Profile:
    """Device 0 of one traced window, in nanoseconds."""
    window: Interval
    modules: List[Tuple[float, float, str]]        # XLA Modules, sorted
    ops: List[Tuple[float, float, str, str]]       # XLA Ops, sorted
    host: Dict[str, List[Tuple[float, float, str]]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int) -> Profile:
    host_trace = trace_mod.load(path)
    plane = _device_0(read_raw(path))
    modules, ops = [], []
    if plane is not None:
        meta = plane["meta"]
        unknown = OpMeta("")
        for s, e, mid in plane["lines"].get("XLA Modules", ()):
            modules.append((s, e, meta.get(mid, unknown).name))
        for s, e, mid in plane["lines"].get(trace_mod.DEVICE_LINE, ()):
            m = meta.get(mid, unknown)
            ops.append((s, e, m.name, m.tf_op or ""))
    modules.sort()
    ops.sort()
    return Profile(host_trace.window, modules, ops, host_trace.host)


def load(path: str) -> Profile:
    """The profile at ``path`` (``.xplane.pb``, or compressed with xz);
    the last two read are kept."""
    return _load(str(path), os.stat(path).st_mtime_ns)


def for_run(rec, out_dir: Optional[pathlib.Path] = None,
            tries: int = 3) -> Optional[Profile]:
    """The profile of the traced run ``rec``: the newest one of the
    cell under the harness's output directory whose window is the one
    the run recorded; None for an untraced run."""
    if rec.trace is None:
        return None
    if out_dir is None:
        from .harness import OUT as out_dir
    paths = sorted(pathlib.Path(out_dir).glob(
        f"{rec.cell}.*/profile/**/*.xplane.pb"),
        key=lambda p: p.stat().st_mtime_ns, reverse=True)
    for path in paths[:tries]:
        prof = load(str(path))
        if prof.window_s == rec.trace["window_s"]:
            return prof
    return None


def decode_steps(p: Profile) -> List[Interval]:
    """``jit_serve_decode`` executions wholly inside the window."""
    lo, hi = p.window
    return [(s, e) for s, e, name in p.modules
            if name.startswith(DECODE_MODULE) and s >= lo and e <= hi]


def _ops_in(p: Profile, steps: Sequence[Interval]):
    """Each step with its ops, ``(start, end, name, tf_op)`` clipped to
    the step."""
    starts = [op[0] for op in p.ops]
    for s, e in steps:
        i = bisect.bisect_left(starts, s)
        ops = []
        while i < len(p.ops) and p.ops[i][0] < e:
            os_, oe, name, tf_op = p.ops[i]
            ops.append((os_, min(oe, e), name, tf_op))
            i += 1
        yield (s, e), ops


def _union_ns(intervals: Sequence[Interval], window: Interval) -> float:
    return sum(t - s for s, t in trace_mod.merge(intervals, window))


def in_scope(scope: str):
    """Accepts the decode step's ops staged under ``scope``."""
    tag = f"/{scope}/"
    return lambda name, tf_op: (tf_op.startswith(DECODE_PATH)
                                and tag in tf_op)


def per_step_ms(p: Profile, keep=None) -> Optional[float]:
    """Device time of the decode steps' ops that ``keep`` accepts (all
    of them by default), as the union of their intervals, per step."""
    steps = decode_steps(p)
    if not steps:
        return None
    total = sum(_union_ns([(s, e) for s, e, name, tf_op in ops
                           if keep is None or keep(name, tf_op)], step)
                for step, ops in _ops_in(p, steps))
    return total / len(steps) * 1e-6


def decode_device_ms(p: Profile) -> Optional[float]:
    """Device time of the decode step, per step, in ms."""
    return per_step_ms(p)


def scope_ms(p: Profile, scope: str) -> Optional[float]:
    """Device time of the decode step's ops in ``scope``, per step."""
    return per_step_ms(p, in_scope(scope))


def _decode_lines(p: Profile) -> List[str]:
    return [k for k, ev in p.host.items()
            if any(n == DECODE_SPAN for _, _, n in ev)]


def _decode_spans(p: Profile) -> List[Interval]:
    return sorted((s, e) for k in _decode_lines(p) for s, e, n in p.host[k]
                  if n == DECODE_SPAN)


def _inside(spans: List[Interval], starts: List[float], t: float) -> bool:
    j = bisect.bisect_right(starts, t) - 1
    return j >= 0 and t < spans[j][1]


def decode_gaps(p: Profile) -> List[Interval]:
    """Device 0's idle gaps in the window whose midpoint lies inside a
    ``serve.decode`` span.  One serving thread opens those spans, so no
    two of them overlap."""
    decode = _decode_spans(p)
    d_starts = [s for s, _ in decode]
    return [(s, t) for s, t in trace_mod.gaps(
                [(a, b, "") for a, b, _, _ in p.ops], p.window)
            if _inside(decode, d_starts, (s + t) / 2)]


def idle_by_phase(p: Profile) -> Dict[str, float]:
    """The time of :func:`decode_gaps`, in ns, by the phase span
    (``serve.input`` .. ``serve.sample``) open during it, or
    ``serve.decode`` between phases.  A gap is cut at the phases'
    edges, since one often runs from the step's end through the finite
    check into the sample."""
    phases = sorted((s, e, n) for k in _decode_lines(p)
                    for s, e, n in p.host[k] if n in PHASES)
    p_starts = [s for s, _, _ in phases]
    out: Dict[str, float] = {}

    def add(name, a, b):
        out[name] = out.get(name, 0.0) + b - a

    for s, t in decode_gaps(p):
        at = s
        j = max(bisect.bisect_right(p_starts, s) - 1, 0)
        while j < len(phases) and phases[j][0] < t:
            a, b, name = phases[j]
            if b > at:
                if a > at:
                    add(DECODE_SPAN, at, a)
                add(name, max(a, at), min(b, t))
                at = min(b, t)
            j += 1
        if at < t:
            add(DECODE_SPAN, at, t)
    return out


def spanned_steps(p: Profile) -> List[Interval]:
    """The decode steps whose midpoint lies inside a ``serve.decode``
    span.  A span open when the profile starts, or still open when it
    stops, is not in the trace; its steps are left out here as its gaps
    are left out of :func:`decode_gaps`."""
    decode = _decode_spans(p)
    d_starts = [s for s, _ in decode]
    return [(s, e) for s, e in decode_steps(p)
            if _inside(decode, d_starts, (s + e) / 2)]


def decode_idle_ms(p: Profile) -> Optional[float]:
    """Idle time of :func:`decode_gaps` per decode step of the same
    spans (:func:`spanned_steps`), in ms."""
    n = len(spanned_steps(p))
    if not n:
        return None
    return sum(t - s for s, t in decode_gaps(p)) / n * 1e-6


def split(p: Profile, top: int = 8) -> Optional[dict]:
    """Per decode step, ms: device time, each scope, what no scope
    covers, the ops that take most of that, and idle time by phase."""
    steps = decode_steps(p)
    if not steps:
        return None
    n = len(steps)
    scoped = lambda name, tf_op: any(  # noqa: E731
        in_scope(s)(name, tf_op) for s in SCOPES)
    out = {"decode_steps": n, "device": per_step_ms(p)}
    for s in SCOPES:
        out[s] = per_step_ms(p, in_scope(s))
    out["unscoped"] = out["device"] - per_step_ms(p, scoped)
    leaf = lambda name, tf_op: not (  # noqa: E731
        scoped(name, tf_op) or name.startswith(CONTAINERS))
    by_op: Dict[str, float] = {}
    for _, ops in _ops_in(p, steps):
        for s, e, name, tf_op in ops:
            if leaf(name, tf_op):
                key = f"{name} {tf_op}".strip()
                by_op[key] = by_op.get(key, 0.0) + e - s
    out["unscoped_ops"] = [[k, v / n * 1e-6] for k, v in sorted(
        by_op.items(), key=lambda kv: -kv[1])[:top]]
    spanned = len(spanned_steps(p))
    idle = {k: v / spanned * 1e-6 for k, v in idle_by_phase(p).items()}
    out["spanned_steps"] = spanned
    out["idle"] = sum(idle.values())
    out["idle_by_phase"] = idle
    return out


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    for path in args:
        print(json.dumps({"profile": path, "split": split(load(path))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

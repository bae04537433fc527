"""The trace reduction, on hand-made intervals and on a small trace
recorded on a v5e chip."""

import pathlib

import pytest

from bench.lib import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _synthetic():
    # Window 0..100 ns.  Device ops: 10-30, 20-40 (overlap), 50-55,
    # 90-120 (clipped to 100), -10-5 (clipped to 0..5).
    ops = [(10, 30, "fusion.1"), (20, 40, "dot.2"), (50, 55, "fusion.1"),
           (90, 120, "copy.3"), (-10, 5, "dot.2")]
    host = {
        "0:python": [(0, 100, "serve.decode"), (41, 49, "PjitFunction(d)"),
                     (60, 89, "ServeEngine._sample")],
        "1:python": [(0, 100, "main.sleep")],
    }
    return tr.Trace(window=(0, 100), device_ops=[ops], host=host)


def test_busy_union_and_gaps():
    t = _synthetic()
    # Busy: 0-5, 10-40, 50-55, 90-100 -> 5 + 30 + 5 + 10 = 50 ns.
    assert tr.busy_ns(t.device_ops[0], t.window) == 50
    assert tr.gaps(t.device_ops[0], t.window) == [(5, 10), (40, 50),
                                                  (55, 90)]


def test_top_ops_clip_to_the_window():
    t = _synthetic()
    # fusion.1: 20 + 5; dot.2: 20 + 5; copy.3: 10.
    got = dict(tr.top_ops(t.device_ops[0], t.window))
    assert got == pytest.approx({"fusion.1": 25e-9, "dot.2": 25e-9,
                                 "copy.3": 10e-9})


def test_gaps_are_named_by_the_innermost_serving_span(monkeypatch):
    monkeypatch.setattr(tr, "SHORT_GAP_NS", 6)
    t = _synthetic()
    # Only line 0 carries serve. spans.  Gap 5-10 is short (< 6).
    assert tr.serving_lines(t) == ["0:python"]
    assert tr.named_gaps(t) == pytest.approx([
        (tr.SHORT_GAP_NAME, 5e-9), ("PjitFunction(d)", 10e-9),
        ("ServeEngine._sample", 35e-9)])
    s = tr.summary(t)
    assert s["busy_s"] == pytest.approx(50e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["idle_gaps"][0] == ["ServeEngine._sample",
                                 pytest.approx(35e-9)]


RECORDED = DATA / "stablelm-code.decode.xplane.pb.xz"


def test_recorded_chip_trace():
    """0.3 s of stablelm-code's decode loop, traced on one v5e.  The
    expected numbers were worked out by counting interval boundaries
    (+1 at each op's start, -1 at its end) instead of merging, and are
    re-derived here the same way."""
    t = tr.load(str(RECORDED))
    assert t.window == (49524554.0, 350553507.0)
    ops = t.device_ops[0]
    assert len(t.device_ops) == 1 and len(ops) == 12008
    lo, hi = t.window
    events = sorted([(max(s, lo), 1) for s, e, _ in ops if e > lo and s < hi]
                    + [(min(e, hi), -1) for s, e, _ in ops
                       if e > lo and s < hi], key=lambda x: (x[0], -x[1]))
    busy, depth, last = 0.0, 0, lo
    for x, d in events:
        if depth > 0:
            busy += x - last
        depth, last = depth + d, x
    assert busy == 198279737.0
    s = tr.summary(t)
    assert s["busy_s"] == pytest.approx(0.198279737)
    assert s["window_s"] == pytest.approx(0.301028953)
    # The decode step's scan over 24 layers is one while loop.
    assert s["device_ops"][0] == ["while.3", pytest.approx(0.191024657)]
    # The serving thread is found by its dispatches; the longest gaps
    # are its host syncs.
    assert tr.serving_lines(t) == ["5:python3"]
    assert s["idle_gaps"][0] == ["np.asarray(jax.Array)",
                                 pytest.approx(0.004854771)]
    idle = sum(s["idle_by_name"].values())
    assert idle == pytest.approx(s["window_s"] - s["busy_s"])

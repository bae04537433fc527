"""The reader of the program's names in a device trace: its protobuf
reader on a trace recorded on a v5e chip, its per-step reductions on
hand-made intervals, and the metrics that read them and the program's
counters."""

import dataclasses
import os
import pathlib
import time

import pytest

from bench.lib import layers
from bench.lib import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"
# 0.3 s of stablelm-code's decode loop, recorded before the engine named
# its steps (both jitted as lambdas).
UNNAMED = DATA / "stablelm-code.decode.xplane.pb.xz"
# 0.5 s at the end of a 15 s stablelm-code window (seed 4410000001),
# recorded on one v5e with the named steps and kernel scopes.  Its
# process had counted 717 tokens, each with one finite-check sync and
# one sample sync.
NAMED = DATA / "stablelm-code.serve.xplane.pb.xz"


@pytest.mark.parametrize("op,tf_op,source", [
    ("fusion.58", "jit(<lambda>)/while/body/closed_call/dot_general:",
     "src/repro/core/gemm.py:559"),
    ("copy.15", "jit(<lambda>)/while/body/dynamic_slice:",
     "src/repro/models/model.py:255"),
    ("while.3", None, "src/repro/models/model.py:255"),
])
def test_op_metadata_of_a_recorded_trace(op, tf_op, source):
    (meta,) = [m for m in layers.op_metadata(str(UNNAMED)) if m.name == op]
    assert meta.tf_op == tf_op
    assert meta.source.endswith(source)


def test_device_ops_are_those_profile_data_reads():
    prof = layers.load(str(UNNAMED))
    want = sorted((s, e) for s, e, _ in tr.load(str(UNNAMED)).device_ops[0])
    assert [(s, e) for s, e, _, _ in prof.ops] == want
    assert all(name and "=" not in name for _, _, name, _ in prof.ops)
    assert len(prof.modules) == 339


@pytest.mark.parametrize("reader", [
    layers.decode_device_ms, layers.decode_idle_ms, layers.split,
    lambda p: layers.scope_ms(p, "gemm"),
    lambda p: layers.scope_ms(p, "attn")],
    ids=["device", "idle", "split", "gemm", "attn"])
def test_a_program_that_names_no_step_reads_nothing(reader):
    """The parent of the named steps: the readers find no
    ``jit_serve_decode`` and return None, without raising."""
    assert reader(layers.load(str(UNNAMED))) is None


def test_a_named_program_labels_its_ops():
    prof = layers.load(str(NAMED))
    names = {n.split("(")[0] for _, _, n in prof.modules}
    assert {"jit_serve_decode", "jit_serve_prefill"} <= names
    steps = layers.decode_steps(prof)
    ops = [op for _, step_ops in layers._ops_in(prof, steps)
           for op in step_ops]
    paths = {tf_op for _, _, _, tf_op in ops if tf_op}
    assert paths and all(p.startswith(layers.DECODE_PATH) for p in paths)
    for scope in layers.SCOPES:
        assert any(f"/{scope}/" in p for p in paths), scope


def _load_metric(name):
    """A metric's ``read``, as ``bench/run.py`` loads it."""
    import importlib.util

    bench = pathlib.Path(__file__).resolve().parents[1]
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  bench / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run.load_metric(bench, name)


@pytest.mark.parametrize("metric,want", [
    ("model.decode_device_ms", 8.474929),
    ("kernel.gemm.decode_ms", 3.966598125),
    ("kernel.attn.decode_ms", 0.7628667499999999),
    ("engine.decode_idle_ms", 8.34133625),
    ("engine.host_syncs_per_token", 2.0),
])
@pytest.mark.parametrize("suffix", ["", ".throughput"])
def test_the_five_readers_on_a_recorded_window(tmp_path, monkeypatch,
                                               metric, want, suffix):
    """Each metric file, as ``bench/run.py`` loads it, finds the profile
    where the harness writes it and reads its fixed value."""
    from bench.lib import harness
    from repro.obs import get_metrics

    d = tmp_path / "stablelm-code.4410000001" / "profile" / "plugins" / "p"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(layers.read_raw(str(NAMED)))
    monkeypatch.setattr(harness, "OUT", tmp_path)
    reg = get_metrics()
    reg.counter("serve.tokens_generated_total").inc(717)
    syncs = reg.counter("serve.host_syncs_total")
    syncs.labels(at="finite").inc(717)
    syncs.labels(at="sample").inc(717)

    read = _load_metric(metric + suffix)
    rec = _Rec("stablelm-code",
               {"window_s": layers.load(str(NAMED)).window_s})
    assert read(rec) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("counted", [True, False],
                         ids=["counted", "parent"])
@pytest.mark.parametrize("suffix", ["", ".throughput"])
def test_host_puts_per_token_reads_the_put_counter(counted, suffix):
    """The recorded window's process counted 717 tokens, 717 token puts
    (prompts and decode tokens) and 661 position puts.  A program that
    keeps no put counter reads nothing."""
    from repro.obs import get_metrics

    reg = get_metrics()
    reg.counter("serve.tokens_generated_total").inc(717)
    if counted:
        puts = reg.counter("serve.host_puts_total")
        puts.labels(what="token").inc(717)
        puts.labels(what="pos").inc(661)
    read = _load_metric("engine.host_puts_per_token" + suffix)
    want = (717 + 661) / 717 if counted else None
    assert read(_Rec("stablelm-code", None)) == want


# ---------------------------------------------------------------------------
# Hand-made intervals
# ---------------------------------------------------------------------------

GEMM = "jit(serve_decode)/while/body/closed_call/gemm/dot_general"
ATTN = "jit(serve_decode)/while/body/closed_call/attn/dot_general"
KV = "jit(serve_decode)/while/body/closed_call/kv_write/dynamic_update_slice"


def _synthetic():
    # Window 0..200.  Two decode steps (20-60, 110-150), a prefill
    # (158-190), and a third decode step that runs past the window.
    modules = [(20, 60, "jit_serve_decode(1)"),
               (110, 150, "jit_serve_decode(1)"),
               (158, 190, "jit_serve_prefill(2)"),
               (195, 230, "jit_serve_decode(1)")]
    ops = [(20, 60, "while.3", ""),          # holds the step's ops
           (20, 30, "fusion.1", GEMM), (25, 35, "fusion.2", GEMM),
           (40, 45, "fusion.3", ATTN), (50, 55, "copy.4", KV),
           (110, 150, "while.3", ""), (110, 130, "fusion.1", GEMM),
           (135, 140, "fusion.3", ATTN), (140, 150, "copy.14", ""),
           (158, 170, "fusion.9", "jit(serve_prefill)/gemm/dot_general"),
           (175, 190, "fusion.9", "jit(serve_prefill)/gemm/dot_general"),
           (195, 230, "while.3", "")]
    serving = [(0, 100, "serve.decode"), (0, 12, "serve.input"),
               (12, 16, "serve.step"), (16, 62, "serve.finite"),
               (62, 70, "serve.sample"),
               (100, 156, "serve.decode"), (100, 105, "serve.input"),
               (105, 108, "serve.step"), (108, 152, "serve.finite"),
               (152, 156, "serve.sample"),
               (156, 192, "serve.prefill"), (156, 158, "serve.input"),
               (158, 160, "serve.step"), (160, 191, "serve.finite"),
               (192, 240, "serve.decode"), (192, 194, "serve.input"),
               (194, 196, "serve.step")]
    host = {"0:python": serving, "1:python": [(0, 200, "main.sleep")]}
    return layers.Profile((0, 200), modules, sorted(ops), host)


def test_decode_steps_lie_wholly_in_the_window():
    assert layers.decode_steps(_synthetic()) == [(20, 60), (110, 150)]


@pytest.mark.parametrize("scope,want_ns", [
    (None, 40 + 40), ("gemm", 15 + 20), ("attn", 5 + 5), ("kv_write", 5)])
def test_per_step_device_time(scope, want_ns):
    p = _synthetic()
    got = (layers.decode_device_ms(p) if scope is None
           else layers.scope_ms(p, scope))
    assert got == pytest.approx(want_ns / 2 * 1e-6)


def test_idle_gaps_are_read_inside_decode_spans_only():
    p = _synthetic()
    # Gaps of device 0: 0-20, 60-110, 150-158, 170-175 (inside the
    # prefill, so not read), 190-195; each cut at the phases' edges,
    # serve.decode between phases.
    assert layers.decode_gaps(p) == [(0, 20), (60, 110), (150, 158),
                                     (190, 195)]
    assert layers.decode_idle_ms(p) == pytest.approx(
        (20 + 50 + 8 + 5) / 2 * 1e-6)
    assert layers.idle_by_phase(p) == {
        "serve.input": 12 + 5 + 2 + 2, "serve.step": 4 + 3 + 1,
        "serve.finite": 4 + 2 + 2 + 2 + 1, "serve.sample": 8 + 4,
        "serve.decode": 30 + 1}
    split = layers.split(p)
    assert split["decode_steps"] == 2
    assert split["idle_by_phase"] == pytest.approx(
        {"serve.input": 10.5e-6, "serve.step": 4e-6, "serve.finite": 5.5e-6,
         "serve.sample": 6e-6, "serve.decode": 15.5e-6})
    assert split["unscoped"] == pytest.approx(15e-6)
    assert split["unscoped_ops"] == [["copy.14", pytest.approx(5e-6)]]


def test_a_decode_span_cut_by_the_profile_drops_its_steps_and_gaps():
    """The first decode span started before the profile, so the trace
    lacks it: neither its gaps nor its step are read."""
    p = _synthetic()
    p.host["0:python"] = [ev for ev in p.host["0:python"]
                          if ev[:2] != (0, 100)]
    assert layers.spanned_steps(p) == [(110, 150)]
    assert layers.decode_gaps(p) == [(150, 158), (190, 195)]
    assert layers.decode_idle_ms(p) == pytest.approx((8 + 5) * 1e-6)
    # Device time does not depend on the spans.
    assert layers.decode_device_ms(p) == pytest.approx(40e-6)


# ---------------------------------------------------------------------------
# Finding the run's profile
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Rec:
    cell: str
    trace: dict


def test_the_run_profile_is_the_newest_with_its_window(tmp_path):
    window_s = layers.load(str(UNNAMED)).window_s
    raw = layers.read_raw(str(UNNAMED))
    paths = []
    for seed in (1, 2):
        d = tmp_path / f"stablelm-code.{seed}" / "profile" / "p" / "t"
        d.mkdir(parents=True)
        paths.append(d / "host.xplane.pb")
        paths[-1].write_bytes(raw)
    now = time.time()
    os.utime(paths[0], (now, now))
    os.utime(paths[1], (now - 60, now - 60))
    got = layers.for_run(_Rec("stablelm-code", {"window_s": window_s}),
                         out_dir=tmp_path)
    assert got is not None and got.window_s == window_s
    assert layers.for_run(_Rec("stablelm-code", {"window_s": 1.0}),
                          out_dir=tmp_path) is None
    assert layers.for_run(_Rec("stablelm-code", None),
                          out_dir=tmp_path) is None

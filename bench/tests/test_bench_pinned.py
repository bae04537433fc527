"""The numbers the benchmark reads, pinned to what they were before the
dense block moved behind the block interface (``bench/reference/``):
weights, reference logits, counts and per-layer readings, bit for bit
for the same seed."""

import hashlib
import importlib.util
import json
import pathlib

import jax
import numpy as np
import pytest

from bench.lib import harness, layers, trace as trace_mod
from bench.tests.test_bench_harness import CHECK_CONFIG, TINY_CONFIG

BENCH = pathlib.Path(__file__).resolve().parents[1]
NAMED = BENCH / "tests" / "data" / "stablelm-code.serve.xplane.pb.xz"
SEED = 2**35 + 1

# sha256 over each leaf's name, dtype, shape and bytes, in name order.
WEIGHTS = {
    ("tiny", "plain"): "d551453073f1862a8cae2edc1cff00cfc4d7f9d286d607e8c4ad395546102a19",
    ("tiny", "program"): "85371957982cadbdcac570d68435458597c45a391a5d5db6b1bbe9dd12651b11",
    ("check", "plain"): "7b0fcbbf5bb0cbae6e97653226fba288a87d71b8a714bcb28e4a13b653ce243b",
    ("check", "program"): "96873bf16108431b7433c2c36c5f8aa71e767d7b82753d0864a455c70bb3fa3a",
}
# sha256 of the float32 logits of TINY_CONFIG at every row of 40 tokens.
LOGITS = {
    "fp32": "f74d50e51d996d4d3805256a17d3ea8bd9369c3ef05d39caffc645fcb4ac7b7e",
    "fp8": "7cc54c074434bb6fd55f3a26b986c7cf4b214a9299eb93d4e28877eeceb1e4c8",
}
COUNTS = {
    "stablelm-2-1.6b": {
        "weight_bytes": 2877493248,
        "prefill_flops": {1: 2877489152, 13: 32490192896, 1500: 3921118625792, 4812: 14144745635840, 7500: 24027628961792},
        "prefill_bytes": {1: 2877693952, 13: 2880102400, 1500: 3178549248, 4812: 3843280896, 7500: 4382773248},
        "decode_flops": {2: 2877685760, 1513: 3174760448, 4900: 3840671744, 7628: 4377018368},
        "decode_bytes": {2: 2877890560, 1513: 3174965248, 4900: 3840876544, 7628: 4377223168},
    },
    "h2o-danube3-4b": {
        "weight_bytes": 7677918720,
        "prefill_flops": {1: 7677911040, 13: 96892477440, 1500: 11562915840000, 4812: 40030862622720, 7500: 66107996160000},
        "prefill_bytes": {1: 7678018560, 13: 7679216640, 1500: 7827678720, 4812: 8158348800, 7500: 8426718720},
        "decode_flops": {2: 7678279680, 1513: 8235294720, 4900: 9483878400, 7628: 10489528320},
        "decode_bytes": {2: 7678110720, 1513: 7817364480, 4900: 8129510400, 7628: 8380922880},
    },
}
READINGS = {
    ("stablelm-2-1.6b", "model.mfu"): 5.601507182753879,
    ("stablelm-2-1.6b", "model.mbu"): 15.986938775510204,
    ("stablelm-2-1.6b", "model.mbu.decode"): 37.563737336773784,
    ("h2o-danube3-4b", "model.mfu"): 15.551727962036258,
    ("h2o-danube3-4b", "model.mbu"): 31.783842762951334,
    ("h2o-danube3-4b", "model.mbu.decode"): 82.32388863025315,
}
# The trace readers on the committed window, with its process's counts:
# 717 tokens, 717 syncs at each of two places, 717 token and 661
# position puts.
TRACE_READINGS = {
    "model.decode_device_ms": 8.474929,
    "kernel.gemm.decode_ms": 3.966598125,
    "kernel.attn.decode_ms": 0.7628667499999999,
    "engine.decode_idle_ms": 8.34133625,
    "engine.host_syncs_per_token": 2.0,
    "engine.host_puts_per_token": 1.9218967921896792,
}


def _digest(tree) -> str:
    h = hashlib.sha256()
    for k in sorted(tree):
        a = np.asarray(tree[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _tiny(tmp_path, raw):
    """A test config, read through the benchmark's own dense block."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "reference").symlink_to(BENCH / "reference")
    (tmp_path / "configs" / "tiny-lm.json").write_text(json.dumps(raw))
    return harness.load_config(tmp_path, "tiny-lm")


def _load_metric(name):
    path = BENCH / "metrics" / f"{name}.py"
    found = importlib.util.spec_from_file_location("pinned_metric", path)
    mod = importlib.util.module_from_spec(found)
    found.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("layout", ["plain", "program"])
@pytest.mark.parametrize("size,raw", [("tiny", TINY_CONFIG),
                                      ("check", CHECK_CONFIG)],
                         ids=["tiny", "check"])
def test_weights_are_the_parents(tmp_path, size, raw, layout):
    _, block, spec = _tiny(tmp_path, raw)
    w = (block.plain_weights(spec, SEED) if layout == "plain" else
         block.program_weights(spec, SEED, block.program_config(spec)))
    assert _digest(w) == WEIGHTS[size, layout]


@pytest.mark.parametrize("precision", ["fp32", "fp8"])
def test_reference_logits_are_the_parents(tmp_path, precision):
    _, block, spec = _tiny(tmp_path, TINY_CONFIG)
    w = block.plain_weights(spec, SEED)
    toks = np.random.default_rng(7).integers(0, spec.vocab, 40)
    with jax.default_matmul_precision("highest"):
        got = block.logits_at(w, spec, toks, np.arange(40),
                              precision=precision)
    assert hashlib.sha256(np.asarray(got, np.float32).tobytes()
                          ).hexdigest() == LOGITS[precision]


@pytest.mark.parametrize("fn", ["weight_bytes", "prefill_flops",
                                "prefill_bytes", "decode_flops",
                                "decode_bytes"])
@pytest.mark.parametrize("name", sorted(COUNTS))
def test_counts_are_the_parents(name, fn):
    _, block, spec = harness.load_config(BENCH, name)
    want = COUNTS[name][fn]
    if fn == "weight_bytes":
        assert block.weight_bytes(spec) == want
    else:
        assert {n: getattr(block, fn)(spec, n) for n in want} == want


def _record(name):
    """A fixed run record at a published config: four requests with
    their token stamps, a traced window and the v5e's peaks."""
    _, block, spec = harness.load_config(BENCH, name)
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    reqs = [harness.ReqRecord(
        uid=i, prompt_len=p, max_new_tokens=len(t), due=d, submitted=d,
        token_times=t, status="done", tokens_at_close=len(t))
        for i, (p, d, t) in enumerate([
            (1500, 0.2, [0.25 + 0.0085 * k for k in range(13)]),
            (4812, 1.0, [1.4 + 0.013 * k for k in range(40)]),
            (7500, 3.0, [3.6 + 0.0129 * k for k in range(90)]),
            (1500, 9.5, [9.6, 9.7])])]
    return harness.RunRecord(
        cell="c", block=block, spec=spec, loop="closed", seconds=10.0,
        requests=reqs, spans=[], peaks=peaks, trace_window=(1.0, 4.5),
        profile_started=2.0)


@pytest.mark.parametrize("name,metric", sorted(READINGS))
def test_count_readings_are_the_parents(name, metric):
    assert _load_metric(metric)(_record(name)) == READINGS[name, metric]


def test_idle_share_of_the_recorded_window_is_the_parents():
    t = trace_mod.summary(trace_mod.load(str(NAMED)))
    assert [t["busy_s"], t["window_s"]] == [0.2897826, 0.500264244]
    rec = type("Rec", (), {"trace": t})()
    assert _load_metric("device.idle_share")(rec) == 42.074093146661106


@pytest.mark.parametrize("suffix", ["", ".throughput"])
@pytest.mark.parametrize("metric", sorted(TRACE_READINGS))
def test_trace_readings_are_the_parents(tmp_path, monkeypatch, metric,
                                        suffix):
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
    puts = reg.counter("serve.host_puts_total")
    puts.labels(what="token").inc(717)
    puts.labels(what="pos").inc(661)
    rec = type("Rec", (), {"cell": "stablelm-code", "trace": {
        "window_s": layers.load(str(NAMED)).window_s}})()
    assert _load_metric(metric + suffix)(rec) == TRACE_READINGS[metric]

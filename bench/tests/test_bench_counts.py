"""Shape counts and the peak table, against counts made by hand."""

import json
import pathlib

import pytest

from bench.lib.harness import load_config

BENCH = pathlib.Path(__file__).resolve().parents[1]


def _spec(name):
    """The published config's spec, and its block as ``counts``."""
    _, block, spec = load_config(BENCH, name)
    return block, spec


# Hand counts from the published sizes, one line per term.
STABLELM = dict(
    # q, k, v, o: 4 x 2048 x 2048; gate, up, down: 3 x 2048 x 5632
    proj_per_token=24 * 2 * (4 * 2048 * 2048 + 3 * 2048 * 5632),
    head=2 * 2048 * 100352,
    # layer weights + two norm gains per layer, head, final norm; bf16
    weight_bytes=2 * (24 * (4 * 2048 * 2048 + 3 * 2048 * 5632 + 2 * 2048)
                      + 2048 * 100352 + 2048),
    kv_per_token=2 * 24 * 2 * 2048,
    attn_per_pair=24 * 4 * 32 * 64,
)
DANUBE = dict(
    # q, o: 3840 x 3840; k, v: 3840 x (8 x 120); gate, up, down: 3840 x 10240
    proj_per_token=24 * 2 * (2 * 3840 * 3840 + 2 * 3840 * 960
                             + 3 * 3840 * 10240),
    head=2 * 3840 * 32000,
    weight_bytes=2 * (24 * (2 * 3840 * 3840 + 2 * 3840 * 960
                            + 3 * 3840 * 10240 + 2 * 3840)
                      + 3840 * 32000 + 3840),
    kv_per_token=2 * 24 * 2 * 960,
    attn_per_pair=24 * 4 * 32 * 120,
)


@pytest.mark.parametrize("name, hand", [("stablelm-2-1.6b", STABLELM),
                                        ("h2o-danube3-4b", DANUBE)])
def test_counts_match_hand_counts(name, hand):
    counts, s = _spec(name)
    assert counts.proj_flops_per_token(s) == hand["proj_per_token"]
    assert counts.head_flops(s) == hand["head"]
    assert counts.weight_bytes(s) == hand["weight_bytes"]
    assert counts.kv_bytes_per_token(s) == hand["kv_per_token"]
    L = 1000
    assert counts.prefill_flops(s, L) == (
        L * hand["proj_per_token"] + hand["attn_per_pair"] * L * (L + 1) // 2
        + hand["head"])
    assert counts.decode_flops(s, 1500) == (
        hand["proj_per_token"] + hand["attn_per_pair"] * 1500 + hand["head"])
    d = s.d_model
    assert counts.prefill_bytes(s, L) == (
        hand["weight_bytes"] + 2 * L * d + L * hand["kv_per_token"])
    assert counts.decode_bytes(s, 1500) == (
        hand["weight_bytes"] + 2 * d + 1500 * hand["kv_per_token"])


def test_stablelm_weight_bytes_are_the_published_size():
    # 1.64 B parameters in bf16, less the embedding table: 2.88 GB read
    # per step, 3.29 GB held.
    counts, s = _spec("stablelm-2-1.6b")
    held = counts.weight_bytes(s) + 2 * s.vocab * s.d_model
    assert abs(held / 1e9 - 3.29) < 0.01


def test_peak_table_is_keyed_by_device_kind():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    assert "cpu" not in peaks


def test_decode_bandwidth_share_reads_token_gaps():
    """model.mbu.decode: the decode steps' bytes over their token gaps,
    from requests that finished before the profiler started."""
    import importlib.util
    from types import SimpleNamespace as NS

    path = BENCH / "metrics" / "model.mbu.decode.py"
    spec = importlib.util.spec_from_file_location("mbu_decode", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    counts, s = _spec("stablelm-2-1.6b")
    req = lambda due, times: NS(due=due, prompt_len=100,  # noqa: E731
                                token_times=times)
    rec = NS(block=counts, spec=s, seconds=10.0, profile_started=5.0,
             peaks={"hbm_bytes_per_s": 819e9},
             requests=[req(0.0, [0.1, 0.11, 0.13]),
                       req(4.0, [4.9, 5.1]),       # ends after the profile
                       req(11.0, [11.1, 11.2])])   # due after the window
    want = 100.0 * (counts.decode_bytes(s, 101) + counts.decode_bytes(s, 102)) \
        / (0.03 * 819e9)
    assert mod.read(rec) == pytest.approx(want)
    rec.requests = rec.requests[1:]
    assert mod.read(rec) is None

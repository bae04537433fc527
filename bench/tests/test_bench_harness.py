"""The harness driven on the CPU at a tiny size, past its look for a
chip: data files found by name, a broken served path caught by the
check, and no result without a TPU."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
LIMIT = json.loads((BENCH / "configs" / "stablelm-2-1.6b.json").read_text()
                   )["widest_gap_limit"]

TINY_CONFIG = {
    "block": "dense",
    "arch": "stablelm-1.6b",
    "changes": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                "head_dim": 16, "d_ff": 128, "vocab_size": 512,
                "q_chunk": 32, "kv_chunk": 32, "param_dtype": "float32",
                "compute_dtype": "float32"},
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
    "torch_dtype": "float32", "widest_gap_limit": LIMIT,
}
# For the tests of the check: large enough that a wrong first choice
# lies clearly below the best logit (the tiny model's logits are too
# flat for that), with 256 served tokens compared.
CHECK_CONFIG = dict(
    TINY_CONFIG, num_hidden_layers=4, hidden_size=256, head_dim=64,
    intermediate_size=512, vocab_size=4096,
    changes=dict(TINY_CONFIG["changes"], n_layers=4, d_model=256,
                 head_dim=64, d_ff=512, vocab_size=4096))
REQUESTS = [{"prompt": 16, "output": 6, "weight": 1},
            {"prompt": 40, "output": 3, "weight": 1}]
MIXES = {
    "tiny-open": {"loop": "open", "rate_rps": 15, "requests": REQUESTS,
                  "block": 10, "batch_size": 2, "drain_s": 10,
                  "check_requests": 4},
    "tiny-closed": {"loop": "closed", "clients": 3, "requests": REQUESTS,
                    "block": 10, "drain_s": 10, "check_requests": 4},
}
CHECK_MIXES = {"tiny-closed": dict(
    MIXES["tiny-closed"], check_requests=8,
    requests=[{"prompt": 64, "output": 32, "weight": 1}])}
NEW_METRIC = '''
def read(rec):
    done = sum(1 for r in rec.requests if r.completed)
    return float(done) if done else None
'''


def _run_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_dir(tmp_path, config=TINY_CONFIG, mixes=MIXES, blocks=None):
    """A benchmark data directory that adds one config, two mixes, one
    metric and the ``blocks`` (name: source) beside copies of the peak
    table, the metric files and the blocks."""
    d = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "reference"):
        (d / sub).mkdir(parents=True)
    # The benchmark's peaks, and made-up ones for the CPU so that the
    # shares of a peak are read here too.
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                    "source": "test"}
    (d / "peaks.json").write_text(json.dumps(peaks))
    for sub in ("metrics", "reference"):
        for f in (BENCH / sub).glob("*.py"):
            shutil.copy(f, d / sub / f.name)
    for name, source in (blocks or {}).items():
        (d / "reference" / f"{name}.py").write_text(source)
    (d / "configs" / "tiny-lm.json").write_text(json.dumps(config))
    for name, mix in mixes.items():
        (d / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    (d / "metrics" / "requests.completed.py").write_text(NEW_METRIC)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-lm", "source": "test",
                         "file": "configs/tiny-lm.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [
        {"name": n, "config": "tiny-lm", "traffic": n, "chips": 1,
         "why": "test"} for n in mixes]
    # The open-loop cell's metrics go to the open tiny mix, the closed
    # cell's to the closed one.
    stands_for = {"stablelm-code": "tiny-open", "danube-longdoc": "tiny-closed"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [stands_for[w] for w in m["workloads"]]
    bench["per_layer"].append(
        {"name": "requests.completed", "unit": "requests", "better": "higher",
         "source": "host_clock", "layer": "engine (serve/engine.py)",
         "moves": "output_tokens_per_s"})
    (d / "BENCHMARK.json").write_text(json.dumps(bench))
    return d, bench


def _execute(tmp_path, cell, trace=False, seconds=1.5, seed=2**35 + 1,
             control=False, config=TINY_CONFIG, mixes=MIXES, blocks=None):
    from bench.lib import harness

    d, bench = _bench_dir(tmp_path, config, mixes, blocks)
    opts = harness.Options(out_dir=tmp_path / "out", trace_len_s=0.5,
                           trace_lead_s=0.2)
    return _run_module().execute(bench, cell, seed, seconds, trace,
                                 time.perf_counter(), opts, bench_dir=d,
                                 control=control)


def _repo_files():
    return {p: p.stat().st_mtime_ns for p in BENCH.rglob("*")
            if p.is_file() and "out" not in p.relative_to(BENCH).parts
            and "__pycache__" not in p.parts}


@pytest.mark.parametrize("cell", sorted(MIXES))
def test_new_files_are_found_by_name(tmp_path, cell):
    """A config, two mixes and a metric added as files: the harness runs
    them, and no file of the benchmark changes."""
    before = _repo_files()
    res = _execute(tmp_path, cell, trace=True)
    assert _repo_files() == before
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["metrics"]["requests.completed"]["value"] >= 1
    assert res["metrics"]["requests.completed"]["unit"] == "requests"
    assert ("model.mbu.decode" in res["metrics"]) == (
        cell == "tiny-open")
    assert list(res)[-1] == "compared"
    assert res["device"]["platform"] == "cpu"


# A block added as a file: it names its sizes its own way (no
# ``n_kv_heads``, ``head_dim`` or ``q_dim``) and computes the dense
# block behind them, so the harness, the check and the count-reading
# metrics find the model only through the block's functions.
TOY_BLOCK = '''
import dataclasses
import functools

from bench.reference import dense


@dataclasses.dataclass(frozen=True)
class ToySpec:
    depth: int
    width: int
    heads: int
    groups: int
    hidden: int
    vocab: int
    dtype: str
    raw: dict = dataclasses.field(default_factory=dict, compare=False)


def load_spec(raw, name):
    t = raw["toy"]
    return ToySpec(t["depth"], t["width"], t["heads"], t["groups"],
                   t["hidden"], t["tokens"], t["dtype"], raw)


@functools.lru_cache(maxsize=None)
def _dense(s):
    return dense.Spec(
        name="toy", n_layers=s.depth, d_model=s.width, n_heads=s.heads,
        n_kv_heads=s.heads // s.groups, head_dim=s.width // s.heads,
        d_ff=s.hidden, vocab=s.vocab, rope_theta=10000.0, norm_eps=1e-5,
        dtype=s.dtype, raw=s.raw)


def program_config(s):
    return dense.program_config(_dense(s))


def plain_weights(s, seed):
    return dense.plain_weights(_dense(s), seed)


def program_weights(s, seed, cfg):
    return dense.program_weights(_dense(s), seed, cfg)


def logits_at(w, s, tokens, rows, precision="fp32"):
    return dense.logits_at(w, _dense(s), tokens, rows, precision)


def prefill_flops(s, n):
    return dense.prefill_flops(_dense(s), n)


def decode_flops(s, n):
    return dense.decode_flops(_dense(s), n)


def prefill_bytes(s, n):
    return dense.prefill_bytes(_dense(s), n)


def decode_bytes(s, n):
    return dense.decode_bytes(_dense(s), n)


def weight_bytes(s):
    return dense.weight_bytes(_dense(s))
'''
TOY_CONFIG = {
    "block": "toy", "arch": TINY_CONFIG["arch"],
    "changes": TINY_CONFIG["changes"], "widest_gap_limit": LIMIT,
    "toy": {"depth": 2, "width": 64, "heads": 4, "groups": 2, "hidden": 128,
            "tokens": 512, "dtype": "float32"},
}


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("cell", sorted(MIXES))
def test_a_block_is_new_files_only(tmp_path, cell, trace):
    """A block and a config that names it, added as files: both tiny
    cells run and are correct, and the count-reading metrics are read
    through the block; no file of the benchmark changes."""
    before = _repo_files()
    res = _execute(tmp_path, cell, trace=trace, config=TOY_CONFIG,
                   blocks={"toy": TOY_BLOCK})
    assert _repo_files() == before
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    want = ({"model.mbu.decode"} if cell == "tiny-open"
            else {"model.mfu", "model.mbu"}) if trace else set()
    got = {m for m in res["metrics"] if m.startswith("model.m")}
    assert got == want
    assert all(res["metrics"][m]["value"] > 0 for m in got)


def test_a_new_block_reads_the_control(tmp_path):
    res = _execute(tmp_path, "tiny-closed", control=True, config=TOY_CONFIG,
                   blocks={"toy": TOY_BLOCK})
    assert res["check"]["control"] == "fp8"
    assert res["compared"]["widest_gap"]["value"] == \
        res["check"]["control_widest_gap"] >= 0.0
    assert res["check"]["tokens"] > 0


def test_a_config_that_names_no_block_is_refused(tmp_path):
    from bench.lib import harness

    config = {k: v for k, v in TINY_CONFIG.items() if k != "block"}
    d, _ = _bench_dir(tmp_path, config)
    with pytest.raises(ValueError, match='"block"'):
        harness.load_config(d, "tiny-lm")


DENSE_FIELDS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
                "d_ff", "q_dim", "kv_dim", "rope_theta", "norm_eps")


def test_only_the_blocks_know_the_dense_layout():
    """Outside ``bench/reference/`` (and the tests of the dense block)
    no file of the benchmark imports ``dense``, ``counts`` or
    ``weights``, or reads a field of the dense spec."""
    import re

    imports = re.compile(
        r"^\s*(from\s+\S+\s+)?import\s.*\b(dense|counts|weights)\b",
        re.M)
    fields = re.compile(r"\.(%s)\b" % "|".join(DENSE_FIELDS))
    files = [p for p in BENCH.rglob("*.py")
             if p.relative_to(BENCH).parts[0] not in ("reference", "tests",
                                                      "out")]
    assert BENCH / "lib" / "harness.py" in files
    bad = {str(p.relative_to(BENCH)): m.group(0)
           for p in files for rx in (imports, fields)
           for m in [rx.search(p.read_text())] if m}
    assert bad == {}
    assert not (BENCH / "lib" / "counts.py").exists()
    assert not (BENCH / "lib" / "spec.py").exists()
    assert not (BENCH / "lib" / "weights.py").exists()


@pytest.mark.parametrize("cell", sorted(MIXES))
def test_end_to_end_metrics_of_a_cell(tmp_path, cell):
    res = _execute(tmp_path, cell)
    names = set(res["metrics"])
    if cell == "tiny-open":
        assert names == {"tpot_p90_ms", "setup_s"}
    else:
        assert names == {"output_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["correct"] is True, res["compared"]


def test_an_altered_token_is_caught(tmp_path, monkeypatch):
    """The served path is broken where a token is produced: every fifth
    sampled token is replaced by its neighbour in the vocabulary."""
    from repro.serve.engine import ServeEngine

    sample = ServeEngine._sample
    calls = {"n": 0}

    def altered(self, logits, temperature):
        tok = sample(self, logits, temperature)
        calls["n"] += 1
        return (tok + 1) % self.cfg.vocab_size if calls["n"] % 5 == 0 \
            else tok

    monkeypatch.setattr(ServeEngine, "_sample", altered)
    res = _execute(tmp_path, "tiny-closed", config=CHECK_CONFIG,
                   mixes=CHECK_MIXES)
    assert res["correct"] is False
    assert res["compared"]["widest_gap"]["value"] > LIMIT


def test_the_fp8_control_is_not_correct(tmp_path):
    """The control in the served tokens' place, through the whole run and
    its check: ``correct`` comes out false on the same limit."""
    res = _execute(tmp_path, "tiny-closed", control=True,
                   config=CHECK_CONFIG, mixes=CHECK_MIXES)
    assert res["correct"] is False
    assert res["check"]["control"] == "fp8"
    assert res["check"]["widest_gap"] <= LIMIT
    assert res["compared"]["widest_gap"]["value"] == \
        res["check"]["control_widest_gap"] > LIMIT


def test_no_result_without_a_tpu(tmp_path):
    """On the CPU, run.py exits non-zero and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "stablelm-code", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_device_checks():
    from types import SimpleNamespace as NS

    run = _run_module()
    peaks = json.loads((BENCH / "peaks.json").read_text())
    tpu = NS(platform="tpu", device_kind="TPU v5 lite")
    assert run.device_error([tpu], 1, peaks) is None
    assert "no TPU" in run.device_error([NS(platform="cpu",
                                            device_kind="cpu")], 1, peaks)
    assert "4 chips" in run.device_error([tpu], 4, peaks)
    assert "not in" in run.device_error(
        [NS(platform="tpu", device_kind="TPU v9")], 1, peaks)

"""The float32 reference against the program's served path, and its
fp8 control, on the CPU at small sizes."""

import json
import pathlib

import jax
import numpy as np
import pytest

from bench.reference import dense
from bench.reference.common import served_rows, widest_gap
from bench.tests.test_bench_harness import CHECK_CONFIG

BENCH = pathlib.Path(__file__).resolve().parents[1]


def spec_of(cfg) -> dense.Spec:
    """The dense block's spec of a program config, read as a
    configuration file under the published key names."""
    raw = {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
           "num_attention_heads": cfg.n_heads,
           "num_key_value_heads": cfg.n_kv_heads,
           "head_dim": cfg.resolved_head_dim,
           "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
           "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
           "torch_dtype": cfg.param_dtype}
    return dense.load_spec(raw, cfg.name)


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "h2o-danube-3-4b"])
def test_reference_matches_teacher_forced_logits(arch):
    """Prefill, then decode through the cache, on the engine's compiled
    steps, against the reference's full forward over the same tokens
    (float32 on both sides, the reduced preset)."""
    from repro.configs import get_reduced
    from repro.serve.engine import ServeEngine

    cfg = get_reduced(arch)
    spec = spec_of(cfg)
    params = dense.program_weights(spec, 3, cfg)
    # Danube's reduced preset has a window of 32: stay inside it.
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 20)
    forced = [5, 77, 300, 9, 411]
    eng = ServeEngine(params, cfg, batch_size=1, max_len=32,
                      warmup_gemms=False)
    got = eng.teacher_forced_logits(prompt, forced)
    w = dense.plain_weights(spec, 3)
    seq = list(prompt) + forced
    with jax.default_matmul_precision("highest"):
        want = dense.logits_at(w, spec, seq,
                               served_rows(len(prompt), 1 + len(forced)))
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())
    assert widest_gap(want, np.argmax(got, -1)) == 0.0


def test_widest_gap():
    ref = np.array([[1.0, 3.0, 2.0], [0.5, 0.0, 0.25]])
    assert widest_gap(ref, [1, 0]) == 0.0
    assert widest_gap(ref, [2, 1]) == 1.0


def test_fp8_control_is_not_correct():
    """The control (the reference in fp8) fails the limit that the
    StableLM and Danube cells are held to, at a size the CPU can run."""
    limit = min(json.loads((BENCH / "configs" / f"{n}.json").read_text())
                ["widest_gap_limit"]
                for n in ("stablelm-2-1.6b", "h2o-danube3-4b"))
    spec = dense.load_spec(CHECK_CONFIG, "control")
    w = dense.plain_weights(spec, 11)
    toks = np.random.default_rng(1).integers(0, spec.vocab, 480)
    rows = np.arange(200, 480)
    with jax.default_matmul_precision("highest"):
        ref = dense.logits_at(w, spec, toks, rows)
        low = dense.logits_at(w, spec, toks, rows, precision="fp8")
    assert widest_gap(ref, np.argmax(ref, -1)) == 0.0
    assert widest_gap(ref, np.argmax(low, -1)) > limit

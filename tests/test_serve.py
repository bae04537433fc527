"""Serving engine: greedy decode = argmax of teacher-forced forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import model as M
from repro.serve.engine import Request, ServeEngine


def test_greedy_matches_forward_argmax():
    cfg = get_reduced("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.arange(8) % cfg.vocab_size
    eng = ServeEngine(params, cfg, batch_size=1, max_len=32)
    eng.submit(Request(uid=1, prompt=prompt, max_new_tokens=5))
    done = eng.run()
    got = done[1].generated

    # reference: step-by-step argmax with full forward each time
    toks = list(prompt)
    want = []
    for _ in range(5):
        logits, _, _ = M.forward(
            params, {"tokens": jnp.asarray([toks], jnp.int32)}, cfg,
            mode="train")
        nxt = int(jnp.argmax(logits[0, -1, :cfg.vocab_size]))
        want.append(nxt)
        toks.append(nxt)
    assert got == want, (got, want)


def test_deterministic_sampling():
    cfg = get_reduced("mamba2-370m")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.arange(6) % cfg.vocab_size
    outs = []
    for _ in range(2):
        eng = ServeEngine(params, cfg, batch_size=1, max_len=24, seed=7)
        eng.submit(Request(uid=1, prompt=prompt, max_new_tokens=4,
                           temperature=0.8))
        outs.append(eng.run()[1].generated)
    assert outs[0] == outs[1]


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
def test_teacher_forced_logits_match_forward(paged):
    """The engine's teacher-forced logits (prefill, then one decode step
    per forced token, through its compiled steps and cache) equal a full
    forward over the same tokens; the paged int8 cache within its
    quantization noise."""
    cfg = get_reduced("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.arange(8) % cfg.vocab_size
    forced = [3, 17, 5]
    eng = ServeEngine(params, cfg, batch_size=1, max_len=32,
                      warmup_gemms=False, paged_kv=paged,
                      kv_page_size=8 if paged else 0)
    got = eng.teacher_forced_logits(prompt, forced)
    assert got.shape == (1 + len(forced), cfg.vocab_size)
    logits, _, _ = M.forward(
        params, {"tokens": jnp.asarray([list(prompt) + forced], jnp.int32)},
        cfg, mode="train")
    want = np.asarray(logits[0, len(prompt) - 1:, :cfg.vocab_size])
    tol = 3e-2 if paged else 1e-3
    np.testing.assert_allclose(got, want, atol=tol * np.abs(want).max())
    if paged:   # the pages bound for scoring went back to the pool
        assert eng.kv_pool.n_free == eng.kv_pool.n_pages


@pytest.mark.parametrize("nan_at, rc", [(None, 0), (0, 1)],
                         ids=["all_done", "one_failed"])
def test_launch_serve_exit_code(monkeypatch, nan_at, rc):
    """The launcher exits 0 only when every request ends ``done``."""
    from repro.launch import serve as launch_serve
    from repro.runtime.fault import FaultPlan

    monkeypatch.setattr(launch_serve, "setup_compile_cache", lambda: None)
    argv = ["--arch", "stablelm-1.6b", "--requests", "2",
            "--prompt-len", "4", "--max-new", "3"]
    plan = FaultPlan(nan_decode_at=() if nan_at is None else (nan_at,))
    with plan:
        assert launch_serve.main(argv) == rc


def test_compile_cache_dir_rule(monkeypatch, tmp_path):
    """The environment's directory is left to JAX; without it the cache
    goes to one fixed directory."""
    from repro.launch import compile_cache as cc

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path / "env"))
    assert cc.setup_compile_cache() == (str(tmp_path / "env"), True)
    assert jax.config.jax_compilation_cache_dir == prev
    monkeypatch.delenv(cc.ENV_VAR)
    monkeypatch.setattr(cc, "DEFAULT_DIR", tmp_path / "fixed")
    try:
        assert cc.setup_compile_cache() == (str(tmp_path / "fixed"), False)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "fixed")
        assert (tmp_path / "fixed").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
def test_decode_step_is_named_and_scoped(paged):
    """The engine's decode step compiles as ``jit_serve_decode``, and its
    ops carry the kernel-family scopes a device trace reads in ``tf_op``:
    ``gemm`` (projections), ``attn`` (attention core) and ``kv_write``
    (the cache update), on the slab and the paged cache alike."""
    import re

    cfg = get_reduced("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(params, cfg, batch_size=1, max_len=32,
                      warmup_gemms=False, paged_kv=paged)
    if paged:
        cache = eng.kv_cache
    else:
        _, cache = eng._prefill(params,
                                {"tokens": jnp.zeros((1, 8), jnp.int32)})
    hlo = eng._decode.lower(
        params, {"tokens": jnp.zeros((1, 1), jnp.int32)}, cache,
        jnp.int32(8)).compile().as_text()
    assert hlo.startswith("HloModule jit_serve_decode")
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    # (Ops of reduction sub-computations carry no path at all.)
    paths = [n for n in op_names if n.startswith("jit(")]
    assert paths and all(n.startswith("jit(serve_decode)/")
                         for n in paths)
    for scope in ("gemm", "attn", "kv_write"):
        assert any(f"/{scope}/" in n for n in op_names), scope

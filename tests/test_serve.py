"""Serving engine: greedy decode = argmax of teacher-forced forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import model as M
from repro.serve.engine import Request, ServeEngine


def _forward_argmax(params, cfg, prompt, n, table=None):
    """``n`` greedy tokens, each the argmax of a full forward over the
    prompt and the tokens before it (codebook 0 of a multi-codebook
    config)."""
    toks = list(prompt)
    want = []
    for _ in range(n):
        ids = jnp.asarray([toks], jnp.int32)
        batch = ({"tokens": ids} if cfg.frontend == "tokens"
                 else {"embeds": table[ids]})
        logits, _, _ = M.forward(params, batch, cfg, mode="train")
        row = logits[0, -1, ..., :cfg.vocab_size]
        if cfg.n_codebooks > 1:
            row = row[0]
        want.append(int(jnp.argmax(row)))
        toks.append(want[-1])
    return want


@pytest.mark.parametrize("arch, paged", [
    ("stablelm-1.6b", False), ("stablelm-1.6b", True),
    ("musicgen-large", False)], ids=["slab", "paged", "embeds-codebooks"])
def test_greedy_matches_forward_argmax(arch, paged):
    """The served greedy tokens, sampled inside the jitted steps and fed
    from one step to the next on the device, equal the argmax of a full
    forward; on the slab and the paged cache, and for an embeds-frontend
    config with four output codebooks."""
    cfg = get_reduced(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.arange(8) % cfg.vocab_size
    eng = ServeEngine(params, cfg, batch_size=1, max_len=32,
                      warmup_gemms=False, paged_kv=paged,
                      kv_page_size=8 if paged else 0)
    eng.submit(Request(uid=1, prompt=prompt, max_new_tokens=5))
    done = eng.run()
    assert done[1].status == "done"
    got = done[1].generated
    want = _forward_argmax(params, cfg, prompt, 5, eng._table)
    assert got == want, (got, want)


def test_sampled_tokens_replay_the_split_categorical_order():
    """Temperature sampling inside the steps draws as the host did: per
    token one split of the engine key, then a categorical draw from the
    row over the temperature; replayed here over the engine's own
    teacher-forced logits."""
    cfg = get_reduced("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.arange(8) % cfg.vocab_size
    eng = ServeEngine(params, cfg, batch_size=1, max_len=32,
                      warmup_gemms=False, seed=11)
    eng.submit(Request(uid=1, prompt=prompt, max_new_tokens=6,
                       temperature=0.8))
    got = eng.run()[1].generated
    rows = eng.teacher_forced_logits(prompt, got[:-1])
    key = jax.random.PRNGKey(11)
    want = []
    for row in rows:
        key, sub = jax.random.split(key)
        want.append(int(jax.random.categorical(sub, row / 0.8)))
    assert got == want, (got, want)
    # The engine's key advanced once per drawn token.
    np.testing.assert_array_equal(eng.key, key)


def test_one_decode_program_serves_every_temperature():
    """Temperature is traced: greedy and sampled requests of one prompt
    length share one compiled prefill and one compiled decode step."""
    cfg = get_reduced("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(params, cfg, batch_size=1, max_len=32,
                      warmup_gemms=False)
    for uid, temperature in enumerate((0.0, 0.8, 1.3)):
        eng.submit(Request(uid=uid, prompt=np.arange(8), max_new_tokens=3,
                           temperature=temperature))
    assert all(r.status == "done" for r in eng.run().values())
    assert eng._prefill._cache_size() == 1
    assert eng._decode._cache_size() == 1


def _recorded(eng, events):
    """Wrap the engine's decode step and its token read to log, in
    order, each dispatch and each read."""
    decode, sample = eng._decode, eng._sample

    def counted_decode(*args):
        events.append("decode")
        return decode(*args)

    def logged_sample(step, temperature):
        events.append("sample")
        return sample(step, temperature)

    eng._decode = counted_decode
    eng._sample = logged_sample


@pytest.mark.parametrize("n", [1, 2, 5])
def test_decode_dispatches_one_step_ahead(n):
    """A request of ``n`` tokens runs exactly ``n - 1`` decode steps, each
    dispatched before the host reads the token it follows; the first
    dispatch comes before the prefill's token is read."""
    cfg = get_reduced("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(params, cfg, batch_size=1, max_len=32,
                      warmup_gemms=False)
    events = []
    _recorded(eng, events)
    eng.submit(Request(uid=1, prompt=np.arange(8), max_new_tokens=n))
    assert len(eng.run()[1].generated) == n
    assert events.count("decode") == n - 1
    assert events == ["decode", "sample"] * (n - 1) + ["sample"]


def test_a_poisoned_step_fails_while_the_next_is_in_flight():
    """Under a fault plan the loop runs ahead as it does in production: a
    NaN at decode step 1 fails the request when its token is read, with
    step 2 already dispatched, and no token of the poisoned step is
    appended."""
    from repro.runtime.fault import FaultPlan

    cfg = get_reduced("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(params, cfg, batch_size=1, max_len=32,
                      warmup_gemms=False)
    events = []
    _recorded(eng, events)
    eng.submit(Request(uid=1, prompt=np.arange(8), max_new_tokens=5))
    with FaultPlan(nan_decode_at=(1,)) as plan:
        req = eng.run()[1]
    assert req.status == "failed" and "nonfinite" in req.error
    assert plan.decode_steps == 3
    assert events == ["decode", "sample"] * 3
    assert len(req.generated) == 2      # the prefill's and step 0's


def test_deadline_mid_decode_keeps_the_tokens_computed(monkeypatch):
    """A deadline that passes after three decode steps fails the request
    and keeps the four tokens computed before it (the prefill's and one
    per step), the first four of the same request served without one."""
    import types

    from repro.serve import engine as engine_mod

    cfg = get_reduced("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.arange(8) % cfg.vocab_size
    clean = ServeEngine(params, cfg, batch_size=1, max_len=32,
                        warmup_gemms=False)
    clean.submit(Request(uid=1, prompt=prompt, max_new_tokens=8))
    want = clean.run()[1].generated

    clock = [0.0]       # one second per decode dispatch
    monkeypatch.setattr(engine_mod, "time", types.SimpleNamespace(
        perf_counter=lambda: clock[0], sleep=lambda s: None))
    eng = ServeEngine(params, cfg, batch_size=1, max_len=32,
                      warmup_gemms=False)
    decode = eng._decode

    def slow_decode(*args):
        clock[0] += 1.0
        return decode(*args)

    eng._decode = slow_decode
    req = Request(uid=1, prompt=prompt, max_new_tokens=8)
    req.deadline_s = 2.5
    eng.submit(req)
    got = eng.run()[1]
    assert got.status == "failed" and "deadline" in got.error
    assert got.generated == want[:4]


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
    greedy = jnp.float32(0)
    if paged:
        cache = eng.kv_cache
    else:
        cache = eng._prefill(params, jnp.zeros((1, 8), jnp.int32), eng.key,
                             greedy, None).cache
    hlo = eng._decode.lower(
        params, jnp.zeros((1, 1), jnp.int32), cache, jnp.int32(8), eng.key,
        greedy, None).compile().as_text()
    assert hlo.startswith("HloModule jit_serve_decode")
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    # (Ops of reduction sub-computations carry no path at all.)
    paths = [n for n in op_names if n.startswith("jit(")]
    assert paths and all(n.startswith("jit(serve_decode)/")
                         for n in paths)
    for scope in ("gemm", "attn", "kv_write"):
        assert any(f"/{scope}/" in n for n in op_names), scope
    # The finite check and the sampling run inside the same module.
    assert "jit(serve_decode)/is_finite" in op_names
    assert "jit(serve_decode)/cond" in op_names

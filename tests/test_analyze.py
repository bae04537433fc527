"""repro.analyze: program verifier, dispatch preflight, AST lint.

Covers ISSUE 10's acceptance criteria: one failing fixture per
diagnostic code (VMEM001/TAG002/QNT003/DIST004/KV005), positive +
noqa-suppressed fixtures per lint rule (RPR001-RPR005), preflight
memoization, the poisoned-cache -> ProgramValidationError dispatch
contract (with the ``analyze.violations_total`` counter), a clean-tree
lint gate, and the BENCH_*.json meta-validation.
"""

import json
import pathlib
import textwrap

import jax.numpy as jnp
import pytest

from repro.analyze import (CODES, Diagnostic, ProgramValidationError,
                           preflight_stats, reset_preflight,
                           validate_attn, validate_cache_entry,
                           validate_dist, validate_program)
from repro.analyze.lint import RULES, lint_paths, lint_source
from repro.core.hardware import V5E
from repro.core.io_model import TileConfig

REPO = pathlib.Path(__file__).resolve().parent.parent

_OK_TILE = TileConfig(bm=256, bn=256, bk=512, order="k_inner")
_HUGE_TILE = TileConfig(bm=16384, bn=16384, bk=16384, order="k_inner")


# ---------------------------------------------------------------------------
# Diagnostics plumbing
# ---------------------------------------------------------------------------

def test_diagnostic_rejects_unknown_code_and_severity():
    with pytest.raises(ValueError, match="unknown diagnostic code"):
        Diagnostic(code="NOPE999", severity="error", message="x")
    with pytest.raises(ValueError, match="severity"):
        Diagnostic(code="VMEM001", severity="fatal", message="x")


def test_program_validation_error_lists_all_diagnostics():
    diags = [Diagnostic(code="VMEM001", severity="error", message="a"),
             Diagnostic(code="TAG002", severity="error", message="b")]
    err = ProgramValidationError(diags)
    assert err.fatal  # must punch through the XLA fallback ladder
    assert err.codes == ("VMEM001", "TAG002")
    assert "VMEM001" in str(err) and "TAG002" in str(err)
    assert isinstance(err, ValueError)


# ---------------------------------------------------------------------------
# Verifier: one failing fixture per code
# ---------------------------------------------------------------------------

def _codes(diags):
    return sorted({d.code for d in diags})


def test_clean_program_validates_clean():
    assert validate_program("rms>bias+gelu", _OK_TILE) == []
    assert validate_program("dqb+bias+silu", _OK_TILE,
                            dtype_b=jnp.int8) == []


def test_vmem001_over_budget_tile():
    diags = validate_program("none", _HUGE_TILE, V5E, dtype=jnp.float32)
    assert _codes(diags) == ["VMEM001"]
    assert diags[0].context["budget"] == int(V5E.vmem_bytes * 0.75)


def test_vmem001_min_plus_broadcast():
    # Fits the plus_times budget but not the tropical kernel's fp32
    # (bm, bk, bn) broadcast buffer.
    tile = TileConfig(bm=1024, bn=1024, bk=1024, order="k_inner")
    assert validate_program("none", tile) == []
    diags = validate_program("none", tile, semiring="min_plus")
    assert _codes(diags) == ["VMEM001"]


def test_tag002_unparseable_and_noncanonical():
    assert _codes(validate_program("not-a-tag", _OK_TILE)) == ["TAG002"]
    # parses, but not canonically ordered -> cache keys would fork
    diags = validate_program("gelu+bias", _OK_TILE)
    assert _codes(diags) == ["TAG002"]
    assert diags[0].context["canonical"] == "bias+gelu"


def test_qnt003_dtype_chain_and_alignment():
    # int8 weights, no dequant drain stage
    diags = validate_program("bias", _OK_TILE, dtype_b=jnp.int8)
    assert _codes(diags) == ["QNT003"]
    # int8 activations without int8 weights / without the "ab" stage
    diags = validate_program("dqb", _OK_TILE, dtype_b=jnp.int8,
                             dtype_a=jnp.int8)
    assert _codes(diags) == ["QNT003"]
    assert validate_program("dqab", _OK_TILE, dtype_b=jnp.int8,
                            dtype_a=jnp.int8) == []
    # per-tile scale block off the lane grid
    diags = validate_program("dqb", _OK_TILE, dtype_b=jnp.int8,
                             scale_block=192)
    assert _codes(diags) == ["QNT003"]
    # act block disagreeing with the weight block
    diags = validate_program("dqab", _OK_TILE, dtype_b=jnp.int8,
                             dtype_a=jnp.int8, scale_block=256,
                             act_block=128)
    assert _codes(diags) == ["QNT003"]


def test_dist004_geometry():
    assert validate_dist("ring", (1, 2, 1), (128, 256, 512)) == []
    assert _codes(validate_dist("bogus", (1, 2, 1),
                                (128, 256, 512))) == ["DIST004"]
    # n does not divide over tp
    assert _codes(validate_dist("ring", (1, 3, 1),
                                (128, 256, 512))) == ["DIST004"]
    # k does not divide over tp*pods
    assert _codes(validate_dist("ring", (1, 2, 3),
                                (128, 256, 512))) == ["DIST004"]
    # per-tile scale block larger than the ring k-chunk (512 / tp=2
    # gives 256-row chunks): a rotated chunk would carry a fractional
    # scale row
    assert _codes(validate_dist("ring", (1, 2, 1), (128, 256, 512),
                                b_block=512)) == ["DIST004"]
    assert validate_dist("ring", (1, 2, 1), (128, 256, 512),
                         b_block=128) == []
    # m is padded to dp, never flagged
    assert validate_dist("ring", (4, 1, 1), (7, 256, 512)) == []


def test_kv005_page_geometry_and_admission():
    from repro.tuning.attention import AttnConfig

    ok = AttnConfig(q_block=128, kv_block=128)
    assert validate_attn(ok, arch="paged_decode") == []
    # page size outside the candidate set
    bad = AttnConfig(q_block=128, kv_block=24)
    assert _codes(validate_attn(bad, arch="paged_decode")) == ["KV005"]
    # flash kv_block off the lane grid
    assert _codes(validate_attn(AttnConfig(q_block=128, kv_block=96),
                                arch="flash")) == ["KV005"]
    # GQA heads must divide
    assert _codes(validate_attn(ok, arch="paged_decode", heads=6,
                                kv_heads=4)) == ["KV005"]
    # pool admission arithmetic: 4 seqs x 1024 tokens at page 128 needs
    # 32 pages
    assert validate_attn(ok, arch="paged_decode", pool_pages=32,
                         batch=4, max_context=1024) == []
    assert _codes(validate_attn(ok, arch="paged_decode", pool_pages=31,
                                batch=4, max_context=1024)) == ["KV005"]
    # block table too short for the admitted context
    assert _codes(validate_attn(ok, arch="paged_decode", table_pages=7,
                                max_context=1024)) == ["KV005"]


def test_every_documented_code_has_a_trigger():
    """The fixtures above must cover the whole CODES table."""
    triggered = set()
    triggered.update(_codes(validate_program("none", _HUGE_TILE)))
    triggered.update(_codes(validate_program("???", None)))
    triggered.update(_codes(validate_program("bias", _OK_TILE,
                                             dtype_b=jnp.int8)))
    triggered.update(_codes(validate_dist("ring", (1, 3, 1),
                                          (8, 256, 512))))
    from repro.tuning.attention import AttnConfig

    triggered.update(_codes(validate_attn(
        AttnConfig(q_block=128, kv_block=24), arch="paged_decode")))
    assert triggered == set(CODES)


# ---------------------------------------------------------------------------
# Dispatch preflight
# ---------------------------------------------------------------------------

def test_preflight_memoizes_per_key():
    from repro.core.gemm import ca_matmul

    reset_preflight()
    x = jnp.ones((8, 64), jnp.float32)
    w = jnp.ones((64, 64), jnp.float32)
    ca_matmul(x, w, mode="interpret")
    s1 = preflight_stats()
    assert s1["validated"] == 1
    ca_matmul(x, w, mode="interpret")  # same key+config: memo hit
    s2 = preflight_stats()
    assert s2["validated"] == 1
    assert s2["hits"] == s1["hits"] + 1


def test_poisoned_cache_entry_raises_vmem001_not_pallas():
    """The acceptance fixture: an over-budget tile smuggled in through
    the persistent tuning cache is rejected by name at dispatch."""
    from repro.core.gemm import ca_matmul
    from repro.obs import get_metrics
    from repro.tuning import get_registry
    from repro.tuning.cache import CacheEntry, cache_key

    reset_preflight()
    reg = get_registry()
    m = n = k = 256
    key = cache_key(m, n, k, "float32", hw=reg.hw)
    reg.cache.put(key, CacheEntry(bm=16384, bn=16384, bk=16384,
                                  order="k_inner", measured_s=1e-3))
    x = jnp.ones((m, k), jnp.float32)
    w = jnp.ones((k, n), jnp.float32)
    with pytest.raises(ProgramValidationError, match="VMEM001"):
        ca_matmul(x, w, mode="interpret")
    snap = get_metrics().snapshot()
    counts = snap["analyze.violations_total"]["labels"]
    assert counts["code=VMEM001"] == 1
    # memoized failure: re-dispatch re-raises without re-counting
    with pytest.raises(ProgramValidationError, match="VMEM001"):
        ca_matmul(x, w, mode="interpret")
    snap = get_metrics().snapshot()
    assert snap["analyze.violations_total"]["labels"]["code=VMEM001"] == 1


def test_dist_matmul_rejects_unknown_schedule():
    from repro.core import dist_matmul
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    a = jnp.ones((8, 16), jnp.float32)
    b = jnp.ones((16, 8), jnp.float32)
    with pytest.raises(ProgramValidationError, match="DIST004"):
        dist_matmul(a, b, mesh, schedule="bogus")


def test_paged_attention_rejects_multi_token_q():
    from repro import kvcache as kvc
    from repro.kvcache.paged import paged_attention

    cache = kvc.make_paged_cache(4, 4, 2, 8, 8, 1, 4)
    q = jnp.zeros((1, 2, 4, 8), jnp.bfloat16)  # q_len=2: not decode
    with pytest.raises(ProgramValidationError, match="KV005"):
        paged_attention(q, cache, mode="xla")


# ---------------------------------------------------------------------------
# Cache entry validation + `cache lint`
# ---------------------------------------------------------------------------

def _entry(bm=256, bn=256, bk=512, order="k_inner"):
    from repro.tuning.cache import CacheEntry

    return CacheEntry(bm=bm, bn=bn, bk=bk, order=order)


def test_validate_cache_entry_gemm():
    good = "v5e/bfloat16/plus_times/none/nn/m256n256k512"
    assert validate_cache_entry(good, _entry()) == []
    # registry-minted keys use hw.name ("tpu-v5e"), not the short alias
    minted = "tpu-v5e/bfloat16/plus_times/none/nn/m256n256k512"
    assert validate_cache_entry(minted, _entry()) == []
    # over-budget tile under the key's own dtype
    key32 = "v5e/float32/plus_times/none/nn/m16384n16384k16384"
    assert "VMEM001" in _codes(validate_cache_entry(
        key32, _entry(16384, 16384, 16384)))
    # stale tag vocabulary
    bad_tag = "v5e/bfloat16/plus_times/dq+bias/nn/m256n256k512"
    assert "TAG002" in _codes(validate_cache_entry(bad_tag, _entry()))
    # malformed key / unknown order
    assert "TAG002" in _codes(validate_cache_entry("v5e/only", _entry()))
    assert "TAG002" in _codes(validate_cache_entry(
        good, _entry(order="zigzag")))
    # composite quant key revalidates the dtype chain
    quant = "v5e/int8w_bf16a/plus_times/dqb/nn/m256n256k512"
    assert validate_cache_entry(quant, _entry()) == []


def test_validate_cache_entry_attn():
    good = "v5e/attn.paged_decode/int8/h8kv2d64/s4096"
    assert validate_cache_entry(good, _entry(128, 128, 128,
                                             order="attn")) == []
    assert "KV005" in _codes(validate_cache_entry(
        good, _entry(128, 24, 24, order="attn")))
    assert "TAG002" in _codes(validate_cache_entry(
        good, _entry(128, 128, 128, order="k_inner")))


def test_cache_lint_flags_and_strips(tmp_path):
    from repro.tuning.cache import TuningCache, lint_cache

    path = tmp_path / "cache.json"
    cache = TuningCache(path, autosave=False)
    cache.put("v5e/bfloat16/plus_times/none/nn/m256n256k512", _entry())
    cache.put("v5e/float32/plus_times/none/nn/m16384n16384k16384",
              _entry(16384, 16384, 16384))
    cache.save()

    flagged = lint_cache(path)
    assert set(flagged) == {
        "v5e/float32/plus_times/none/nn/m16384n16384k16384"}
    # strip mode removes the bad entry and keeps the good one
    lint_cache(path, strip=True)
    reloaded = TuningCache(path, autosave=False)
    assert len(reloaded) == 1
    assert lint_cache(path) == {}


def test_cache_lint_cli(tmp_path, capsys):
    from repro.tuning.cache import TuningCache, main

    path = tmp_path / "cache.json"
    cache = TuningCache(path, autosave=False)
    cache.put("v5e/float32/plus_times/none/nn/m16384n16384k16384",
              _entry(16384, 16384, 16384))
    cache.save()
    assert main(["lint", str(path)]) == 1
    assert "VMEM001" in capsys.readouterr().out
    assert main(["lint", str(path), "--strip"]) == 0
    assert main(["lint", str(path)]) == 0


# ---------------------------------------------------------------------------
# AST lint rules: positive + noqa fixtures
# ---------------------------------------------------------------------------

def _lint(path, src):
    findings, suppressed = lint_source(pathlib.Path(path),
                                       textwrap.dedent(src))
    return [f.code for f in findings], [f.code for f in suppressed]


def test_rpr001_registry_bypass_and_noqa():
    src = """
    from repro.kernels import fused_matmul

    def run(a, b):
        return fused_matmul(a, b)
    """
    assert _lint("benchmarks/fix.py", src) == (["RPR001"], [])
    # the dispatch layers may call kernels directly
    assert _lint("src/repro/kernels/fix.py", src) == ([], [])
    src_noqa = src.replace("return fused_matmul(a, b)",
                           "return fused_matmul(a, b)  # repro: noqa RPR001")
    assert _lint("benchmarks/fix.py", src_noqa) == ([], ["RPR001"])


def test_rpr002_missing_ledger_record():
    src = """
    def dispatch(a, b):
        from repro.kernels import ops as kops
        return kops.fused_matmul(a, b)
    """
    assert _lint("src/repro/core/fix.py", src) == (["RPR002"], [])
    recorded = """
    def dispatch(a, b):
        from repro.kernels import ops as kops
        led = _ledger()
        led.record_gemm(1, 1, 1, None)
        return kops.fused_matmul(a, b)
    """
    assert _lint("src/repro/core/fix.py", recorded) == ([], [])
    # outside the dispatch layers the rule does not fire (RPR001 does)
    assert "RPR002" not in _lint("src/repro/serve/fix.py", src)[0]


def test_rpr003_assert_validation():
    src = """
    def public(x):
        assert x > 0, x
        return x

    def _private(x):
        assert x > 0
        return x

    class C:
        def __post_init__(self):
            if True:
                assert self.x
    """
    codes, _ = _lint("src/repro/serve/fix.py", src)
    assert codes == ["RPR003", "RPR003"]  # public leading + post_init
    noqa = src.replace("assert x > 0, x",
                       "assert x > 0, x  # repro: noqa RPR003")
    codes, supp = _lint("src/repro/serve/fix.py", noqa)
    assert codes == ["RPR003"] and supp == ["RPR003"]
    # mid-function asserts in public functions are not validation gates
    mid = """
    def public(x):
        y = x + 1
        assert y > 1
        return y
    """
    assert _lint("src/repro/serve/fix.py", mid) == ([], [])


def test_rpr004_overbroad_except():
    src = """
    def f():
        try:
            g()
        except:
            pass

    def h():
        try:
            g()
        except Exception:
            return None

    def ok_reraise():
        try:
            g()
        except Exception as e:
            raise RuntimeError("wrapped") from e

    def ok_guard():
        try:
            g()
        except Exception as e:
            _note_fallback("stage", e)

    def ok_narrow():
        try:
            g()
        except ValueError:
            return None
    """
    codes, _ = _lint("src/repro/serve/fix.py", src)
    assert codes == ["RPR004", "RPR004"]


def test_rpr005_unlocked_global_mutation():
    src = """
    _flag = False

    def set_flag(v):
        global _flag
        _flag = v

    def set_flag_locked(v):
        global _flag
        with _lock:
            _flag = v
    """
    codes, _ = _lint("src/repro/serve/fix.py", src)
    assert codes == ["RPR005"]


def test_lint_clean_on_repo_tree():
    """Acceptance: `python -m repro.analyze lint src/ benchmarks/` exits
    0 on the final tree."""
    findings, _supp, n_files = lint_paths([str(REPO / "src"),
                                           str(REPO / "benchmarks")])
    assert n_files > 50
    assert findings == [], "\n".join(str(f) for f in findings)


def test_lint_cli_json_report(tmp_path):
    from repro.analyze.lint import main

    bad = tmp_path / "benchmarks" / "fix.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("from repro.kernels import fused_matmul\n"
                   "y = fused_matmul(1, 2)\n")
    out = tmp_path / "report.json"
    rc = main([str(bad), "--format", "json", "--output", str(out)])
    assert rc == 1
    report = json.loads(out.read_text())
    assert report["rules"] == RULES
    assert [f["code"] for f in report["findings"]] == ["RPR001"]


# ---------------------------------------------------------------------------
# BENCH gate workloads validate clean (meta-test)
# ---------------------------------------------------------------------------

def _bench_dtypes(ds):
    if "w_" in ds:
        w, a = ds.split("w_", 1)
        a = a[:-1] if a.endswith("a") else a
        return a, w, (w if a == "int8" else None)
    return ds, None, None


def test_bench_gemm_workloads_validate_clean():
    results = json.loads((REPO / "BENCH_gemm.json").read_text())["results"]
    assert results
    for r in results:
        c = r["config"]
        tile = TileConfig(bm=c["bm"], bn=c["bn"], bk=c["bk"],
                          order=c["order"])
        dtype, dtype_b, dtype_a = _bench_dtypes(r["dtype"])
        diags = validate_program(r.get("epilogue") or "none", tile,
                                 dtype=dtype, dtype_b=dtype_b,
                                 dtype_a=dtype_a)
        assert diags == [], (r["kind"], [str(d) for d in diags])


def test_bench_attn_workloads_validate_clean():
    from repro.analyze.validate import validate_paged_dispatch
    from repro.tuning.attention import _PAGE_CANDIDATES

    results = json.loads((REPO / "BENCH_attn.json").read_text())["results"]
    assert results
    for r in results:
        page = r.get("page")
        if page is None:
            continue
        if r["kind"] == "kv_bytes":
            # pool-sizing entries use registry-grade page sizes
            assert page in _PAGE_CANDIDATES, r
        else:
            # dispatch-grade check (bench harness runs toy pages)
            B, NP, Hkv, D = r["shape"][0], r["shape"][1], r["shape"][2], \
                r["shape"][-1]
            diags = validate_paged_dispatch(q_shape=(B, 1, 2 * Hkv, D),
                                            page=page, n_heads=2 * Hkv,
                                            kv_heads=Hkv)
            assert diags == [], [str(d) for d in diags]


# ---------------------------------------------------------------------------
# report CLI
# ---------------------------------------------------------------------------

def test_report_cli_one_arch(capsys):
    from repro.analyze.__main__ import main

    rc = main(["report", "--arch", "stablelm-1.6b"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "stablelm-1.6b" in out and "0 diagnostic(s)" in out

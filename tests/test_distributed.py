"""Distributed CA-GEMM schedules (subprocess: forces 8 host devices)."""

import os
import subprocess
import sys

import pytest


def test_all_schedules_correct():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.core._dist_check", "8"],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    lines = [l for l in out.stdout.splitlines() if l.startswith(("OK", "FAIL"))]
    assert len(lines) >= 27
    assert all(l.startswith("OK") for l in lines), out.stdout
    # the load-bearing checks by name (the count alone could be padded)
    for want in ("ring_unpipelined 2d", "summa25d 3d", "ragged-m37",
                 "w8a8-ride", "ledger dist records",
                 "ring interpret-local-step"):
        assert any(want in l for l in lines), (want, out.stdout)


def test_cost_model_properties():
    """Eq. 6-derived distributed cost model sanity (no devices needed)."""
    from repro.core import choose_schedule, estimate_cost

    # ring and allgather move the same bytes; ring overlaps
    r = estimate_cost("ring", 16384, 16384, 16384, 2, 16, 16)
    g = estimate_cost("allgather", 16384, 16384, 16384, 2, 16, 16)
    assert abs(r.comm_bytes - g.comm_bytes) < 1e-6
    assert r.time_s <= g.time_s

    # 2.5D reduces intra-pod traffic with pods
    c1 = estimate_cost("summa25d", 16384, 16384, 16384, 2, 16, 16, pods=2)
    assert c1.comm_bytes < 2 * g.comm_bytes

    # auto never loses to the explicit candidates
    best = choose_schedule(16384, 16384, 16384, 2, 16, 16, pods=2)
    for s in ("allgather", "ring", "summa25d"):
        assert best.time_s <= estimate_cost(
            s, 16384, 16384, 16384, 2, 16, 16, pods=2).time_s + 1e-12

    # the model is shape-aware: different (dp, tp) splits move different
    # bytes at the same chip count
    small_tp = estimate_cost("ring", 8192, 8192, 8192, 2, dp=16, tp=2)
    big_tp = estimate_cost("ring", 8192, 8192, 8192, 2, dp=2, tp=16)
    assert small_tp.comm_bytes != big_tp.comm_bytes


def test_cost_model_pipelining():
    """The per-step model distinguishes the pipelined ring from the
    unpipelined ablation — in both bytes and time."""
    from repro.core import estimate_cost

    m = n = k = 16384
    g = 16
    r = estimate_cost("ring", m, n, k, 2, 16, g)
    u = estimate_cost("ring_unpipelined", m, n, k, 2, 16, g)

    # pipelining removes exactly the dead final rotation: (g-1)/g bytes
    assert r.steps == u.steps == g
    assert abs(r.comm_bytes / u.comm_bytes - (g - 1) / g) < 1e-12
    assert r.overlapped and not u.overlapped

    # per-step decomposition: the pipelined time is fill + (g-1) max
    # terms; the unpipelined time serializes every step's compute + comm
    want_r = r.step_compute_s + (g - 1) * max(r.step_compute_s, r.step_comm_s)
    assert abs(r.time_s - want_r) < 1e-15
    want_u = g * u.step_compute_s + u.comm_s
    assert abs(u.time_s - want_u) < 1e-15
    assert r.time_s < u.time_s

    # compute-bound regime (grow n: ring comm is n-independent, compute
    # is not): the pipelined ring's time collapses to pure compute —
    # comm fully hidden, the paper's Sec. 4 claim
    cb = estimate_cost("ring", m, 1 << 20, k, 2, 16, g)
    assert cb.step_comm_s < cb.step_compute_s
    assert abs(cb.time_s - cb.steps * cb.step_compute_s) < 1e-12

    # a single-step ring (tp=1) has no comm at all
    one = estimate_cost("ring", m, n, k, 2, 16, 1)
    assert one.steps == 1 and one.comm_bytes == 0


def test_local_resolution_registry_key():
    """The per-step local GEMM resolves under the *local* shape's cache
    key — pinned literally so the keying can't silently drift."""
    import jax.numpy as jnp

    from repro.core import dist_local_resolution

    res, tag, loc = dist_local_resolution(
        "ring", 256, 512, 512, dp=2, tp=4, dtype=jnp.float32)
    assert loc == (128, 128, 128, 4)
    assert tag == "none"
    assert res.key == "tpu-v5e/float32/plus_times/none/nn/m128n128k128"
    assert res.source in ("analytic", "cache", "autotune")

    # w8a8 variant: composite dtype + both-operand dequant tag
    res8, tag8, loc8 = dist_local_resolution(
        "ring", 256, 512, 512, dp=2, tp=4, dtype=jnp.float32,
        dtype_b=jnp.int8, dtype_a=jnp.int8)
    assert loc8 == loc
    assert tag8 == "dqab"
    assert res8.key == "tpu-v5e/int8w_int8a/plus_times/dqab/nn/m128n128k128"

    # allgather's local step contracts the full (unsharded-by-tp) k
    resag, _, locag = dist_local_resolution(
        "allgather", 256, 512, 512, dp=2, tp=4, dtype=jnp.float32)
    assert locag == (128, 128, 512, 1)
    assert "k512" in resag.key


def test_dist_ledger_record():
    """record_dist: planned wire bytes match the cost model exactly (the
    invariant BENCH_dist.json's ledger gate re-checks end-to-end)."""
    from repro.core import estimate_cost
    from repro.obs.ledger import GemmLedger

    led = GemmLedger(enabled=True)
    led.record_dist(schedule="ring", m=256, n=512, k=512, dp=2, tp=4,
                    dtype="float32", steps=4,
                    planned_bytes=estimate_cost(
                        "ring", 256, 512, 512, 4, 2, 4).comm_bytes,
                    planned_flops=2.0 * 256 * 512 * 512)
    (rec,) = led.records
    assert rec.schedule == "ring" and rec.mesh == "dp2.tp4"
    assert rec.planned_bytes == estimate_cost(
        "ring", 256, 512, 512, 4, 2, 4).comm_bytes
    assert rec.key == "dist.ring|none|float32|256x512x512|dp2.tp4"
    d = rec.to_dict()
    assert d["schedule"] == "ring" and d["planned_bytes"] == rec.planned_bytes


def test_chaos_fallback_dist_matmul():
    """An injected kernel failure inside a ring step degrades the dispatch
    to the GSPMD reference — same semantics, one fallback counter tick."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import dist_matmul, gemm_fallback
    from repro.launch.mesh import make_mesh
    from repro.obs import get_metrics
    from repro.runtime.fault import FaultPlan

    def fallback_total():
        snap = get_metrics().snapshot()
        m = snap.get("gemm.fallback_total")
        return m.get("labels", {}).get("stage=dist_matmul", 0) if m else 0

    mesh = make_mesh((1, 1), ("data", "model"))
    a = jnp.asarray(np.random.RandomState(0).randn(8, 16), jnp.float32)
    b = jnp.asarray(np.random.RandomState(1).randn(16, 8), jnp.float32)
    want = np.asarray(jnp.dot(a, b))

    before = fallback_total()
    with gemm_fallback(True), FaultPlan(kernel_fail_at=(0,)) as plan:
        got = dist_matmul(a, b, mesh, schedule="ring")
    assert plan.injected == [("kernel", 0)]
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-5)
    assert fallback_total() == before + 1

    # fallback disabled (the suite default): the injection propagates
    with FaultPlan(kernel_fail_at=(0,)):
        with pytest.raises(Exception, match="injected kernel failure"):
            dist_matmul(a, b, mesh, schedule="ring")

    # fatal injections never degrade, even with the fallback gate open
    with gemm_fallback(True), FaultPlan(kernel_fatal_at=(0,)):
        with pytest.raises(Exception, match="fatal"):
            dist_matmul(a, b, mesh, schedule="ring")


def test_shard_gemm_workloads():
    """Warmup shape rewriting: global workloads -> per-device ring-step
    local shapes (non-divisible entries drop, tags pass through)."""
    from repro.tuning import shard_gemm_workloads

    loads = [(37, 512, 512, "none", "nn"),
             (37, 512, 512, "res", "nn", "int8"),
             (37, 90, 512, "none", "nn")]    # n=90 not divisible by tp=4
    out = shard_gemm_workloads(loads, 2, 4)
    assert out == [(19, 128, 128, "none", "nn"),
                   (19, 128, 128, "res", "nn", "int8")]
    # pods divide k one level further
    assert shard_gemm_workloads([(64, 512, 512, "none", "nn")], 2, 4,
                                pods=2) == [(32, 128, 64, "none", "nn")]


def test_dist_operand_specs():
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.sharding.rules import dist_operand_specs

    mesh = make_mesh((1, 1), ("data", "model"))
    specs = dist_operand_specs(("embed", "qkv"), (64, 64), mesh)
    assert specs == (P("data", "model"), P(None, "model"),
                     P("data", "model"))
    # output axis need not map to the model axis (wo-style defs ride too)
    assert dist_operand_specs(("qkv", "embed"), (64, 64), mesh) is not None
    # non-2D weights (or meshes without the tp axis) cannot ride
    assert dist_operand_specs(("embed",), (64,), mesh) is None
    no_tp = make_mesh((1,), ("data",))
    assert dist_operand_specs(("embed", "qkv"), (64, 64), no_tp) is None

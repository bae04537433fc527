"""Self-test for the distributed GEMM schedules, run in a subprocess with
forced CPU host devices (so the main test session keeps 1 device).

Usage: python -m repro.core._dist_check [ndev]
Prints "OK <schedule> ..." lines; exits nonzero on mismatch.
"""

import os
import sys

if __name__ == "__main__":
    ndev = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    # A CPU rehearsal by design: pin the platform so it never takes a chip.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={ndev} "
        + os.environ.get("XLA_FLAGS", "")
    )

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import distributed as dist  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def _check(name, got, want, failures, atol=1e-3, rtol=1e-4):
    got = np.asarray(got)
    ok = got.shape == want.shape and np.allclose(got, want, atol=atol,
                                                 rtol=rtol)
    print(f"{'OK' if ok else 'FAIL'} {name} maxerr="
          f"{np.abs(got - want).max() if got.shape == want.shape else 'shape'}")
    return failures + (0 if ok else 1)


def main(ndev: int) -> int:
    assert len(jax.devices()) == ndev, jax.devices()
    failures = 0
    rng = np.random.RandomState(0)
    m, k, n = 64, 128, 96

    # 2D mesh (data=2, model=ndev//2)
    mesh = make_mesh((2, ndev // 2), ("data", "model"))
    a = jnp.asarray(rng.randn(m, k), jnp.float32)
    b = jnp.asarray(rng.randn(k, n), jnp.float32)
    want = np.asarray(a) @ np.asarray(b)
    for sched in ("allgather", "ring", "ring_unpipelined", "auto"):
        got = dist.dist_matmul(a, b, mesh, schedule=sched)
        failures = _check(f"{sched} 2d", got, want, failures)

    # 3D mesh (pod=2, data=2, model=ndev//4) — 2.5D schedule
    if ndev >= 8:
        mesh3 = make_mesh((2, 2, ndev // 4), ("pod", "data", "model"))
        for sched in ("ring", "ring_unpipelined", "summa25d", "allgather"):
            got = dist.dist_matmul(a, b, mesh3, schedule=sched,
                                   pod_axis="pod")
            failures = _check(f"{sched} 3d", got, want, failures)

    # Reference (GSPMD) path agrees too.
    got = dist.dist_matmul_reference(a, b, mesh)
    failures = _check("gspmd-reference", got, want, failures)

    # out_dtype honored by both the schedules and the reference
    # (satellite: the reference used to hardcode astype(a.dtype)).
    got = dist.dist_matmul(a, b, mesh, schedule="ring",
                           out_dtype=jnp.bfloat16)
    ref = dist.dist_matmul_reference(a, b, mesh, out_dtype=jnp.bfloat16)
    ok = (got.dtype == jnp.bfloat16 and ref.dtype == jnp.bfloat16
          and np.allclose(np.asarray(got, np.float32),
                          np.asarray(ref, np.float32), atol=1e-3, rtol=2e-2))
    print(f"{'OK' if ok else 'FAIL'} out_dtype bf16 ring+reference")
    failures += 0 if ok else 1

    # Ragged m: rows pad to a dp multiple inside dist_matmul, slice back.
    ar = jnp.asarray(rng.randn(37, k), jnp.float32)
    want_r = np.asarray(ar) @ np.asarray(b)
    for sched in ("ring", "allgather"):
        got = dist.dist_matmul(ar, b, mesh, schedule=sched)
        failures = _check(f"{sched} ragged-m37", got, want_r, failures)

    # int8 weights ride the ring (per-channel and per-tile scales):
    # parity vs the dequant oracle.
    from repro.quant import quantize

    for block in (0, 16):  # k/(tp*pods)=32 on the 2D mesh -> block 16 fits
        qb = quantize(b, axis=-2, block=block)
        want_q = np.asarray(ar) @ np.asarray(qb.dequantize())
        for sched in ("ring", "allgather"):
            got = dist.dist_matmul(ar, qb, mesh, schedule=sched)
            failures = _check(f"{sched} int8w block={block}", got, want_q,
                              failures, atol=5e-3, rtol=1e-3)
        ref = dist.dist_matmul_reference(ar, qb, mesh)
        failures = _check(f"reference int8w block={block}", ref, want_q,
                          failures, atol=5e-3, rtol=1e-3)

    # w8a8: a per-tensor static act scale makes A ride the ring as int8
    # payload (1 B/element on the wire); parity vs the fake-quant oracle.
    import dataclasses as _dc

    from repro.quant.scales import fake_quant_activation

    act_scale = jnp.asarray(np.abs(np.asarray(ar)).max() / 127.0,
                            jnp.float32)
    for block in (0, 16):
        qb = _dc.replace(quantize(b, axis=-2, block=block),
                         act_scale=act_scale, act_block=0)
        af = fake_quant_activation(ar, act_scale, 0)
        want_q = np.asarray(af) @ np.asarray(qb.dequantize())
        for sched in ("ring", "allgather"):
            got = dist.dist_matmul(ar, qb, mesh, schedule=sched)
            failures = _check(f"{sched} w8a8-ride block={block}", got,
                              want_q, failures, atol=5e-3, rtol=1e-3)
        ref = dist.dist_matmul_reference(ar, qb, mesh)
        failures = _check(f"reference w8a8-ride block={block}", ref, want_q,
                          failures, atol=5e-3, rtol=1e-3)

    # Ledger: one `dist` record per dispatch whose planned bytes exactly
    # equal the Eq. 6 analog (the expression BENCH_dist.json gates on) and
    # whose tile came from the registry keyed by the *local* shape.
    from repro.obs.ledger import GemmLedger, set_ledger, reset_ledger

    led = GemmLedger(enabled=True)
    set_ledger(led)
    try:
        dist.dist_matmul(a, b, mesh, schedule="ring")
        qb = _dc.replace(quantize(b, axis=-2, block=0),
                         act_scale=act_scale, act_block=0)
        dist.dist_matmul(a, qb, mesh, schedule="ring")
        recs = [r for r in led.records
                if getattr(r, "schedule", None) == "ring"]
        tp = mesh.shape["model"]
        dense_bytes = dist.estimate_cost(
            "ring", m, n, k, 4, mesh.shape["data"], tp).comm_bytes
        w8a8_bytes = dist.estimate_cost(
            "ring", m, n, k, 1, mesh.shape["data"], tp).comm_bytes
        ok = (len(recs) == 2
              and recs[0].planned_bytes == dense_bytes
              and recs[1].planned_bytes == w8a8_bytes
              and recs[0].dtype == "float32"
              and recs[1].dtype == "int8w_int8a"
              and recs[1].tag == "dqab"
              and recs[0].config["kstep"] == k // tp
              and all(r.config_source in ("analytic", "cache", "autotune")
                      for r in recs))
        print(f"{'OK' if ok else 'FAIL'} ledger dist records "
              f"(bytes {recs[0].planned_bytes:.0f}/{dense_bytes:.0f}, "
              f"{recs[1].planned_bytes:.0f}/{w8a8_bytes:.0f})")
        failures += 0 if ok else 1
    finally:
        reset_ledger()

    # Registry-tuned local step actually dispatches through the Pallas
    # kernel body in interpret mode (the CPU stand-in for the TPU path).
    from repro.core.gemm import gemm_mode

    with gemm_mode("interpret"):
        got = dist.dist_matmul(a, b, mesh, schedule="ring")
    failures = _check("ring interpret-local-step", got, want, failures)

    # choose_schedule consumes registry-resolved local tiles: the compute
    # term must come from the roofline, not peak FLOPs alone.
    c = dist.choose_schedule(m, n, k, 4, 2, ndev // 2, use_registry=True,
                             dtype=jnp.float32)
    c0 = dist.estimate_cost(c.schedule, m, n, k, 4, 2, ndev // 2,
                            dtype=jnp.float32)
    ok = c.step_compute_s >= c0.step_compute_s > 0 or c.steps == 1
    print(f"{'OK' if ok else 'FAIL'} choose_schedule use_registry "
          f"({c.schedule}, step_compute {c.step_compute_s:.3e})")
    failures += 0 if ok else 1
    return failures


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]) if len(sys.argv) > 1 else 8))

"""End-to-end smoke for the tensor-parallel decode step, run in a
subprocess with forced host devices (the main test session keeps 1).

Usage: python -m repro.serve._tp_check [ndev]
Prints "OK ..." lines; exits nonzero on mismatch.
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

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import distributed as dist  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.obs.ledger import GemmLedger, reset_ledger, set_ledger  # noqa: E402
from repro.quant import quantize  # noqa: E402
from repro.serve import tp  # noqa: E402


def _ok(name, cond, detail=""):
    print(f"{'OK' if cond else 'FAIL'} {name}{' ' + detail if detail else ''}")
    return 0 if cond else 1


def main(ndev: int) -> int:
    assert len(jax.devices()) == ndev, jax.devices()
    failures = 0
    cfg = tp.TpDecodeConfig(d_model=64, n_heads=4, d_ff=128)
    mesh = make_mesh((2, ndev // 2), ("data", "model"))
    key = jax.random.PRNGKey(0)
    params = tp.init_tp_params(cfg, key)
    B, T = 4, 3

    # Dense parity: T decode steps with a growing KV cache, TP step vs
    # the single-host oracle.
    placed = tp.place_tp_params(params, cfg, mesh)
    rng = np.random.RandomState(1)
    xs = [jnp.asarray(rng.randn(B, cfg.d_model) * 0.1, jnp.float32)
          for _ in range(T)]
    kv = kv_ref = None
    maxerr = 0.0
    for x in xs:
        y, kv = tp.tp_decode_step(placed, x, kv, cfg, mesh)
        y_ref, kv_ref = tp.tp_decode_reference(params, x, kv_ref, cfg)
        maxerr = max(maxerr, float(np.abs(np.asarray(y)
                                          - np.asarray(y_ref)).max()))
    failures += _ok("tp-decode dense parity", maxerr < 1e-3,
                    f"maxerr={maxerr:.2e} T={T}")
    failures += _ok("tp-decode kv shape",
                    kv[0].shape == (B, T, cfg.n_heads, cfg.head_dim),
                    str(kv[0].shape))

    # Quantized (int8w) parity: every projection weight quantized
    # per-channel, riding the ring with its scales.
    qparams = {k: (quantize(v, axis=-2, block=0) if v.ndim == 2 else v)
               for k, v in params.items()}
    qplaced = tp.place_tp_params(qparams, cfg, mesh)
    kv = kv_ref = None
    maxerr = 0.0
    for x in xs:
        y, kv = tp.tp_decode_step(qplaced, x, kv, cfg, mesh)
        y_ref, kv_ref = tp.tp_decode_reference(qparams, x, kv_ref, cfg)
        maxerr = max(maxerr, float(np.abs(np.asarray(y)
                                          - np.asarray(y_ref)).max()))
    failures += _ok("tp-decode int8w parity", maxerr < 5e-3,
                    f"maxerr={maxerr:.2e}")

    # w8a8: attach a per-tensor static act scale to the MLP projections —
    # their activations ride the ring as int8 payload.
    act_scale = jnp.asarray(0.05, jnp.float32)
    q8params = dict(qparams)
    for name in ("mlp/w_gate", "mlp/w_up", "mlp/w_down"):
        q8params[name] = dataclasses.replace(
            qparams[name], act_scale=act_scale, act_block=0)
    q8placed = tp.place_tp_params(q8params, cfg, mesh)
    y, _ = tp.tp_decode_step(q8placed, xs[0], None, cfg, mesh)
    y_ref, _ = tp.tp_decode_reference(q8params, xs[0], None, cfg)
    maxerr = float(np.abs(np.asarray(y) - np.asarray(y_ref)).max())
    failures += _ok("tp-decode w8a8-ride parity", maxerr < 5e-3,
                    f"maxerr={maxerr:.2e}")

    # Ledger: one `dist` record per projection (7 per step: q/k/v/o,
    # gate/up/down), planned bytes matching the cost model exactly.
    led = GemmLedger(enabled=True)
    set_ledger(led)
    try:
        tp.tp_decode_step(placed, xs[0], None, cfg, mesh)
        recs = [r for r in led.records
                if getattr(r, "schedule", None) == "ring"]
        d, f = cfg.d_model, cfg.d_ff
        want_bytes = dist.estimate_cost(
            "ring", B, d, d, 4, mesh.shape["data"],
            mesh.shape["model"]).comm_bytes
        qkv = [r for r in recs if (r.m, r.n, r.k) == (B, d, d)]
        failures += _ok("tp-decode ledger records", len(recs) == 7,
                        f"n={len(recs)}")
        failures += _ok(
            "tp-decode ledger planned bytes",
            len(qkv) == 4 and all(r.planned_bytes == want_bytes
                                  for r in qkv),
            f"{[r.planned_bytes for r in qkv]} vs {want_bytes}")
        failures += _ok(
            "tp-decode ledger shapes",
            {(r.m, r.n, r.k) for r in recs}
            == {(B, d, d), (B, f, d), (B, d, f)})
        failures += _ok(
            "tp-decode ledger sources",
            all(r.config_source in ("analytic", "cache", "autotune")
                for r in recs))
    finally:
        reset_ledger()
    return failures


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]) if len(sys.argv) > 1 else 8))

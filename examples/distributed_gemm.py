"""Distributed CA-GEMM demo: all three schedules on forced host devices.

Run the paper's chain-vs-broadcast comparison at cluster scale: the ring
(PE-chain analog) and all-gather (broadcast analog) schedules compute the
same product; the artifact is the collective profile, printed from the
compiled HLO of each.

  PYTHONPATH=src python examples/distributed_gemm.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dist_matmul, estimate_cost
from repro.launch import hlo_analysis as H


def main():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(256, 512), jnp.float32)
    b = jnp.asarray(rng.randn(512, 384), jnp.float32)
    want = np.asarray(a) @ np.asarray(b)

    for sched in ("allgather", "ring"):
        f = jax.jit(lambda x, y, s=sched: dist_matmul(x, y, mesh, schedule=s))
        got = f(a, b)
        comp = f.lower(a, b).compile()
        cost = H.analyze_hlo_text(comp.as_text())
        model = estimate_cost(sched, 256, 384, 512, 4, 2, 4)
        ok = np.allclose(np.asarray(got), want, atol=1e-3)
        print(f"{sched:10s} correct={ok}  "
              f"collectives={cost.coll_counts}  "
              f"hlo_coll_bytes={cost.coll_bytes:.2e}  "
              f"(model {model.comm_bytes:.2e})")


if __name__ == "__main__":
    main()

"""Paper Fig. 7 analog: strong scaling with parallelism.

On the FPGA, N_c scaled until chiplet crossings throttled frequency.  The
TPU analog scales chips: we compile the distributed CA-GEMM (ring schedule)
for growing mesh sizes in a subprocess (forced host devices), read the
collective bytes from the partitioned HLO, and project GOp/s at v5e
constants — showing where the schedule leaves the compute-bound regime
(the 'frequency cliff' analog is the ICI roofline).
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp

from repro.core import V5E, estimate_cost
from benchmarks.common import emit

N = 16384

_SUB = r"""
import os, sys, json
ndev = int(sys.argv[1])
# A CPU rehearsal by design: pin the platform so it never takes a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
sys.path.insert(0, sys.argv[2])
import jax, jax.numpy as jnp
from repro.core import dist_matmul
from repro.launch import hlo_analysis as H

from repro.launch.mesh import make_mesh
mesh = make_mesh((1, ndev), ("data", "model"))
N = int(sys.argv[3])

def f(a, b):
    return dist_matmul(a, b, mesh, schedule="ring")

comp = jax.jit(f).lower(
    jax.ShapeDtypeStruct((N, N), jnp.bfloat16),
    jax.ShapeDtypeStruct((N, N), jnp.bfloat16)).compile()
c = H.analyze_hlo_text(comp.as_text())
print(json.dumps({"coll": c.coll_bytes, "flops": c.flops}))
"""


def run(max_dev: int = 8, full: bool = False):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    sizes = [1, 2, 4, 8]
    if full:
        sizes += [16, 32]
    n = N if full else 2048
    for ndev in sizes:
        if ndev == 1:
            coll = 0.0
            flops = 2.0 * n ** 3
        else:
            out = subprocess.run(
                [sys.executable, "-c", _SUB, str(ndev), src, str(n)],
                capture_output=True, text=True, timeout=570)
            if out.returncode != 0:
                emit(f"fig7_chips{ndev}", 0.0, f"FAIL:{out.stderr[-100:]}")
                continue
            d = json.loads(out.stdout.strip().splitlines()[-1])
            coll, flops = d["coll"], d["flops"]
        compute_s = flops / V5E.peak_flops(jnp.bfloat16)
        comm_s = coll / V5E.ici_bandwidth
        t = max(compute_s, comm_s)  # ring overlaps (paper's chain)
        gops = 2.0 * n ** 3 / t / 1e9 if t else 0.0
        model = estimate_cost("ring", n, n, n, 2, 1, ndev)
        emit(f"fig7_chips{ndev}", 0.0,
             f"hlo_coll={coll:.3e}B;model_coll={model.comm_bytes:.3e}B;"
             f"proj={gops:.0f}GOp/s;bound="
             f"{'comm' if comm_s > compute_s else 'compute'}")


if __name__ == "__main__":
    run()

"""Distributed-GEMM scaling bench: pipelined ring vs baselines.

The paper's Sec. 4 argument, lifted one level: the 2-D PE grid collapses
to a neighbor-only 1-D chain whose transfers hide behind compute; here
the chain is the inter-chip ring of ``core.distributed.dist_matmul``,
run on 8 forced host devices (the CPU stand-in for an ICI ring).  Three
schedules on one shape:

- **ring** — the double-buffered pipelined chain: g-1 ``ppermute`` hops,
  each issued before the local GEMM that hides it;
- **ring_unpipelined** — the ablation: same math, g hops including the
  dead final rotation, transfer and compute serialized;
- **allgather** — the broadcast baseline the paper rejects: materialize
  the full A panel, then one local GEMM.

Per schedule this records numerics vs the oracle, planned comm bytes and
wall-clock from the cost model (the Eq. 6 analog ``estimate_cost``, with
the local step's tile resolved through the tuning registry), measured
median wall time, and the *compiled* HLO's collective bytes/counts
(``launch.hlo_analysis``) — so the planned-vs-lowered gap is a tracked
number.  A **w8a8 ring** record rides int8 activation payloads (1
B/element on the wire) against the same dense ring.  The obs ledger's
``dist`` record is corroborated byte-for-byte against the plan.

``--check-baseline`` (the CI gate) enforces: pipelined ring comm bytes
<= allgather's; pipelined/unpipelined byte ratio == (g-1)/g; int8-ride /
dense ring wire ratio <= INT8_RIDE_GATE; compiled pipelined HLO
collective bytes <= unpipelined's; ledger == plan; and per-record
non-regression vs the committed ``BENCH_dist.json``.
"""

import os
import sys

NDEV = 8
if __name__ == "__main__":
    # A CPU rehearsal by design: pin the platform so it never takes a chip.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={NDEV} "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import pathlib       # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402

from repro.core import V5E, distributed as dist  # noqa: E402
from repro.launch.hlo_analysis import analyze_hlo_text  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.obs.ledger import GemmLedger, reset_ledger, set_ledger  # noqa: E402
from repro.quant import quantize  # noqa: E402
from benchmarks.common import time_call  # noqa: E402

# v1: schedules {ring, ring_unpipelined, allgather} + the w8a8 int8-ride
# ring on (M, N, K) over a (DP, TP) mesh: numerics, planned comm bytes +
# modeled seconds (registry-resolved local tile), measured median
# seconds, compiled-HLO collective bytes/counts, ledger corroboration;
# top-level "ratios" section carries the gated comparisons.
JSON_SCHEMA_VERSION = 1
DEFAULT_JSON_PATH = "BENCH_dist.json"

M, N, K = 256, 512, 512
DP, TP = 2, NDEV // 2

# The int8 activation ride replaces a 4 B/element wire payload with
# 1 B/element (+ nothing: scales are per-tensor and stay off the ring);
# the planned ratio is 0.25 — gate with headroom.
INT8_RIDE_GATE = 0.6


def _mesh():
    return make_mesh((DP, TP), ("data", "model"))


def _planned(schedule, itemsize, dtype, dtype_b=None, dtype_a=None):
    """Cost with the local step's tile resolved through the registry."""
    res, tag, (mloc, nloc, kloc, steps) = dist.dist_local_resolution(
        schedule, M, N, K, dp=DP, tp=TP, dtype=dtype,
        dtype_b=dtype_b, dtype_a=dtype_a)
    cost = dist.estimate_cost(schedule, M, N, K, itemsize, DP, TP,
                              dtype=dtype, tile=res.config,
                              dtype_b=dtype_b, dtype_a=dtype_a)
    return cost, res, tag, (mloc, nloc, kloc, steps)


def _ledger_bytes(a, b, mesh, schedule):
    """Eager dispatch under an enabled ledger; returns the recorded
    planned wire bytes (must equal the cost model's exactly)."""
    led = GemmLedger(enabled=True)
    set_ledger(led)
    try:
        dist.dist_matmul(a, b, mesh, schedule=schedule)
        recs = [r for r in led.records
                if getattr(r, "schedule", None) == schedule]
        return float(recs[-1].planned_bytes) if recs else None
    finally:
        reset_ledger()


def run(records):
    mesh = _mesh()
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(M, K), jnp.float32)
    b = jnp.asarray(rng.randn(K, N), jnp.float32)
    want = np.asarray(a) @ np.asarray(b)

    cases = [("ring", b, None), ("ring_unpipelined", b, None),
             ("allgather", b, None)]
    act_scale = jnp.asarray(np.abs(np.asarray(a)).max() / 127.0, jnp.float32)
    qb = dataclasses.replace(quantize(b, axis=-2, block=0),
                             act_scale=act_scale, act_block=0)
    cases.append(("ring", qb, "w8a8"))

    for schedule, w, variant in cases:
        if variant == "w8a8":
            itemsize, dtype_b, dtype_a = 1, jnp.int8, jnp.int8
            oracle = np.asarray(a) @ np.asarray(qb.dequantize())
            atol = np.abs(oracle).max() * 2e-2
        else:
            itemsize, dtype_b, dtype_a = 4, None, None
            oracle, atol = want, 1e-2
        cost, res, tag, (mloc, nloc, kloc, steps) = _planned(
            schedule, itemsize, jnp.float32, dtype_b, dtype_a)

        fn = jax.jit(lambda x, y, s=schedule: dist.dist_matmul(
            x, y, mesh, schedule=s))
        got = fn(a, w)
        maxerr = float(np.abs(np.asarray(got) - oracle).max())
        hlo = analyze_hlo_text(fn.lower(a, w).compile().as_text())
        median_s = time_call(fn, a, w, warmup=2, iters=5) / 1e6
        ledger_bytes = _ledger_bytes(a, w, mesh, schedule)

        name = f"{schedule}{'+w8a8' if variant else ''}"
        rec = {
            "kind": "dist",
            "schedule": schedule,
            "variant": variant or "dense",
            "shape": [M, N, K],
            "dtype": "int8w_int8a" if variant == "w8a8" else "float32",
            "mesh": {"dp": DP, "tp": TP},
            "steps": steps,
            "local_shape": [mloc, nloc, kloc],
            "config": {"bm": res.config.bm, "bn": res.config.bn,
                       "bk": res.config.bk, "order": res.config.order},
            "config_source": res.source,
            "epilogue_tag": tag,
            "planned_comm_bytes": float(cost.comm_bytes),
            "planned_comm_s": float(cost.comm_s),
            "planned_step_compute_s": float(cost.step_compute_s),
            "overlapped": bool(cost.overlapped),
            "model_predicted_s": float(cost.time_s),
            "median_s": float(median_s),
            "hlo_coll_bytes_per_device": float(hlo.coll_bytes),
            "hlo_coll_counts": dict(hlo.coll_counts),
            "ledger_planned_bytes": ledger_bytes,
            "numerics_maxerr": maxerr,
            "numerics_ok": bool(maxerr < atol),
        }
        records.append(rec)
        print(f"{name},{median_s * 1e6:.1f}us,planned_comm="
              f"{cost.comm_bytes:.0f}B,model={cost.time_s:.3e}s,"
              f"hlo_coll={hlo.coll_bytes:.0f}B,"
              f"maxerr={maxerr:.2e},tile={res.config.bm}x{res.config.bn}"
              f"x{res.config.bk},src={res.source}")
    return records


def _by(records, schedule, variant="dense"):
    for r in records:
        if r["schedule"] == schedule and r["variant"] == variant:
            return r
    return None


def ratios_section(records):
    ring = _by(records, "ring")
    unpip = _by(records, "ring_unpipelined")
    ag = _by(records, "allgather")
    w8a8 = _by(records, "ring", "w8a8")
    g = ring["steps"]
    return {
        "ring_vs_allgather_comm_bytes":
            ring["planned_comm_bytes"] / ag["planned_comm_bytes"],
        "pipelined_vs_unpipelined_comm_bytes":
            ring["planned_comm_bytes"] / unpip["planned_comm_bytes"],
        "expected_pipelined_vs_unpipelined": (g - 1) / g,
        "int8_ride_vs_dense_comm_bytes":
            w8a8["planned_comm_bytes"] / ring["planned_comm_bytes"],
        "pipelined_vs_unpipelined_model_s":
            ring["model_predicted_s"] / unpip["model_predicted_s"],
        "hlo_pipelined_vs_unpipelined_coll_bytes":
            (ring["hlo_coll_bytes_per_device"]
             / unpip["hlo_coll_bytes_per_device"]
             if unpip["hlo_coll_bytes_per_device"] else None),
    }


def model_error_section(records):
    entries = []
    for rec in records:
        med, pred = rec.get("median_s"), rec.get("model_predicted_s")
        if not med or not pred:
            continue
        entries.append({
            "schedule": rec["schedule"], "variant": rec["variant"],
            "shape": rec["shape"], "measured_s": float(med),
            "model_predicted_s": float(pred),
            "error_ratio": float(med) / float(pred),
        })
    section = {"n_entries": len(entries), "entries": entries}
    if entries:
        r = np.asarray([e["error_ratio"] for e in entries])
        section["geomean_error_ratio"] = float(np.exp(np.log(r).mean()))
        section["min_error_ratio"] = float(r.min())
        section["max_error_ratio"] = float(r.max())
    return section


def _baseline_index(baseline):
    if not baseline:
        return {}
    return {(r["schedule"], r["variant"], tuple(r["shape"])): r
            for r in baseline.get("results", [])}


def check_baseline(records, base_idx) -> int:
    failures = 0
    ring = _by(records, "ring")
    unpip = _by(records, "ring_unpipelined")
    ag = _by(records, "allgather")
    w8a8 = _by(records, "ring", "w8a8")
    g = ring["steps"]

    for rec in records:
        if not rec["numerics_ok"]:
            print(f"REGRESSION {rec['schedule']}/{rec['variant']}: numerics "
                  f"maxerr {rec['numerics_maxerr']:.2e}")
            failures += 1
        if rec["ledger_planned_bytes"] != rec["planned_comm_bytes"]:
            print(f"REGRESSION {rec['schedule']}/{rec['variant']}: ledger "
                  f"bytes {rec['ledger_planned_bytes']} != plan "
                  f"{rec['planned_comm_bytes']:.0f}")
            failures += 1
        base = base_idx.get((rec["schedule"], rec["variant"],
                             tuple(rec["shape"])))
        if base is not None and rec["planned_comm_bytes"] \
                > base["planned_comm_bytes"]:
            print(f"REGRESSION {rec['schedule']}/{rec['variant']}: planned "
                  f"comm bytes {rec['planned_comm_bytes']:.0f} > baseline "
                  f"{base['planned_comm_bytes']:.0f}")
            failures += 1

    # The paper's claim, as invariants: the chain never moves more than
    # the broadcast, and pipelining removes exactly the dead rotation.
    if ring["planned_comm_bytes"] > ag["planned_comm_bytes"]:
        print(f"REGRESSION: ring comm {ring['planned_comm_bytes']:.0f}B > "
              f"allgather {ag['planned_comm_bytes']:.0f}B")
        failures += 1
    got = ring["planned_comm_bytes"] / unpip["planned_comm_bytes"]
    if abs(got - (g - 1) / g) > 1e-9:
        print(f"REGRESSION: pipelined/unpipelined byte ratio {got:.4f} != "
              f"(g-1)/g = {(g - 1) / g:.4f}")
        failures += 1
    if ring["model_predicted_s"] > unpip["model_predicted_s"]:
        print("REGRESSION: pipelined ring modeled slower than unpipelined")
        failures += 1
    ride = w8a8["planned_comm_bytes"] / ring["planned_comm_bytes"]
    if ride > INT8_RIDE_GATE:
        print(f"REGRESSION: int8-ride/dense wire ratio {ride:.3f} > "
              f"{INT8_RIDE_GATE}")
        failures += 1
    if ring["hlo_coll_bytes_per_device"] \
            > unpip["hlo_coll_bytes_per_device"]:
        print(f"REGRESSION: compiled pipelined coll bytes "
              f"{ring['hlo_coll_bytes_per_device']:.0f} > unpipelined "
              f"{unpip['hlo_coll_bytes_per_device']:.0f}")
        failures += 1
    if not failures:
        print("# baseline check OK (ring <= allgather bytes; pipelined/"
              "unpipelined == (g-1)/g; int8 ride <= gate; HLO coll bytes "
              "pipelined <= unpipelined; ledger == plan)")
    return failures


def write_json(records, path=DEFAULT_JSON_PATH):
    payload = {
        "schema": JSON_SCHEMA_VERSION,
        "benchmark": "dist",
        "hardware_model": V5E.name,
        "backend": jax.default_backend(),
        "devices": NDEV,
        "results": records,
        "ratios": ratios_section(records),
        "model_error": model_error_section(records),
    }
    p = pathlib.Path(path)
    p.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    print(f"# wrote {len(records)} records to {p}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", default=DEFAULT_JSON_PATH,
                    help="output path for machine-readable results "
                         "('' disables)")
    ap.add_argument("--baseline", default=DEFAULT_JSON_PATH,
                    help="committed baseline JSON to compare against")
    ap.add_argument("--check-baseline", action="store_true",
                    help="exit nonzero on any gate failure (CI)")
    args = ap.parse_args(argv)

    base_idx = {}
    try:
        base_idx = _baseline_index(
            json.loads(pathlib.Path(args.baseline).read_text()))
    except (OSError, ValueError):
        if args.check_baseline:
            print(f"# no readable baseline at {args.baseline!r}; gates "
                  "check only the in-run invariants")

    records = []
    run(records)
    rc = 0
    if args.check_baseline:
        rc = check_baseline(records, base_idx)
    if args.json:
        write_json(records, args.json)
    return rc


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; it names a
configuration (``bench/configs/<config>.json``, whose ``"block"`` key
names the module ``bench/reference/<block>.py`` that gives its spec,
weights, reference and counts) and a traffic mix
(``bench/traffic/<mix>.json``).  Set-up makes the weights from the seed,
builds the engine and warms every prompt length of the mix; the window
then offers the mix's load for ``--seconds``; afterwards the served
tokens of a sample of requests are compared with the float32 reference.
``--control`` puts the fp8 control in the served tokens' place in that
comparison, to show that the limit fails it; the benchmark's own runs
never pass it.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (each read by ``bench/metrics/<name>.py``) from the
same run with a profile of a few seconds of the window.  The last line
of stdout is one JSON object; the numbers compared for ``correct`` are
the last lines of stderr and the last key of that object.

Exits non-zero, printing no result, when JAX's first device is not a
TPU, when there are fewer devices than the cell asks for, or when the
device kind is missing from ``bench/peaks.json``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def _log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_metric(bench_dir: pathlib.Path, name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def execute(bench: dict, cell_name: str, seed: int, seconds: float,
            trace: bool, t_start: float, opts=None,
            bench_dir: pathlib.Path = BENCH, control: bool = False) -> dict:
    """One run of a cell, past the look for a chip: returns the result
    line as a dict (``compared`` last).  With ``control`` the fp8
    control takes the served tokens' place in the check: at each
    position of the same prompts and served tokens, the token that the
    fp8 forward puts first is held to the same limit, so ``correct``
    has to come out false."""
    import jax

    from bench.lib import check, endtoend, harness

    cell = harness.find_cell(bench, cell_name)
    opts = opts or harness.Options()
    opts.trace = trace
    run = harness.run_cell(cell, seed, seconds, t_start, opts, bench_dir)
    rec = run["record"]
    _log(f"compilations in the window: {run['compiles_in_window']}")
    late = run["lateness"]
    _log("generator lateness s: median "
         f"{statistics.median(late) if late else 0.0} max "
         f"{max(late) if late else 0.0} over {len(late)} submissions")

    metrics = {}
    if trace:
        for m in bench["per_layer"]:
            if applies(m, cell_name):
                value = load_metric(bench_dir, m["name"])(rec)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if not applies(m, cell_name):
                continue
            value = (run["setup_s"] if m["name"] == "setup_s"
                     else endtoend.METRICS[m["name"]](rec))
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    devices = jax.devices()[:cell["chips"]]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run["peak_bytes"]}
    breakdown = None
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        breakdown = {"device_ops": rec.trace["device_ops"],
                     "idle_gaps": rec.trace["idle_gaps"]}

    missing = sum(1 for r in rec.requests if r.due < seconds
                  and r.first is None and rec.loop == "open")
    failed = run["failed"] + missing
    limit = float(run["config"]["widest_gap_limit"])
    uids = check.sample(run["served"], run["mix"].check_requests, seed)
    got = check.widest_gaps(rec.block, rec.spec, seed, run["served"], uids,
                            control=control)
    gap = got["control_widest_gap"] if control else got["widest_gap"]
    compared = {
        "widest_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
    }
    if not got["requests"]:
        _log("no finished request to check")
    correct = gap <= limit and failed == 0 and got["requests"] >= 1

    summary = {
        "cell": cell_name, "seed": seed, "setup_s": run["setup_s"],
        "compiles_in_window": run["compiles_in_window"],
        "lateness_s": late, "check": got,
        "requests": [vars(r) for r in rec.requests],
        "trace": rec.trace,
    }
    (run["out_dir"] / "run.json").write_text(json.dumps(summary, indent=1))

    result = {"correct": bool(correct),
              "attempted": sum(1 for r in rec.requests if r.due < seconds),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # What the check read; with ``control`` the program's own gap too.
    result["check"] = dict(got, control="fp8" if control else None)
    result["compared"] = compared
    return result


def device_error(devices, chips: int, peaks: dict):
    """Why this run may not go on these devices, or None."""
    if devices[0].platform != "tpu":
        return (f"no TPU: JAX's first device is {devices[0].platform}; "
                "this benchmark runs only on the chip")
    if len(devices) < chips:
        return f"the cell asks for {chips} chips, JAX has {len(devices)}"
    if devices[0].device_kind not in peaks:
        return (f"device kind {devices[0].device_kind!r} is not in "
                "bench/peaks.json")
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="check the fp8 control in the served tokens' "
                    "place; correct must then come out false (not a "
                    "benchmark run)")
    args = ap.parse_args(argv)

    from bench.lib import harness

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)

    import jax

    peaks = json.loads((BENCH / "peaks.json").read_text())
    error = device_error(jax.devices(), cell["chips"], peaks)
    if error:
        _log(error)
        return 1
    harness.setup_compile_cache()

    result = execute(bench, args.workload, args.seed, args.seconds,
                     bool(args.trace), T_START, control=args.control)
    for name, c in result["compared"].items():
        _log(f"compared {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

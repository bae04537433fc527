#!/usr/bin/env python3
"""Find the knee of an open-loop cell: runs at several offered rates.

    python3 bench/tools/sweep.py --workload stablelm-code --seconds 30 \\
        --rates 8 2.2 --seed 5

Each rate is one run of the cell with its mix's ``rate_rps`` replaced,
in this process.  For each it prints the offered and completed request
rates, the requests still queued when the window closed, and the
median and 90th percentile time to first token.  The knee is the
highest rate at which the backlog does not grow through the window; for
a FIFO engine serving one request at a time, an overload run's
completed rate gives it, and a run at 0.8 of it should end with a short
queue.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)

    import jax

    from bench.lib import endtoend, harness, traffic

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 1
    harness.setup_compile_cache()
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    base = traffic.load_mix(harness.BENCH / "traffic"
                            / f"{cell['traffic']}.json")
    if base.loop != "open":
        print("sweep: the cell's loop is not open", file=sys.stderr)
        return 1
    for i, rate in enumerate(args.rates):
        mix = dataclasses.replace(base, rate_rps=rate, drain_s=0.0)
        run = harness.run_cell(cell, args.seed + i, args.seconds,
                               time.perf_counter(), harness.Options(),
                               mix=mix)
        rec = run["record"]
        due = [r for r in rec.requests if r.due < rec.seconds]
        started = [r for r in due if r.first is not None
                   and r.first < rec.seconds]
        done = [r for r in due if r.completed
                and r.token_times[-1] < rec.seconds]
        ttft = [r.first - r.due for r in started]
        busy = sum(r.token_times[-1] - r.token_times[0] for r in done)
        line = dict(
            rate_offered=rate, seconds=rec.seconds, due=len(due),
            completed_in_window=len(done),
            completed_rate=len(done) / rec.seconds,
            queued_at_close=len(due) - len(started),
            ttft_p50_s=float(np.percentile(ttft, 50)) if ttft else None,
            ttft_p90_s=float(np.percentile(ttft, 90)) if ttft else None,
            output_tokens_per_s=endtoend.output_tokens_per_s(rec),
            mean_decode_s_per_request=busy / len(done) if done else None)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

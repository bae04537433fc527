#!/usr/bin/env python3
"""Readings that the correctness limit is set from, for one cell.

    python3 bench/tools/calibrate.py --workload <cell> --seconds 15 \\
        --seeds 101 102 ... [--control]

In one process, for each seed, one run as ``bench/run.py`` makes it
(set-up, a window of ``--seconds`` at the cell's own load, the check
over the same sample of finished requests).  Without ``--control`` the
check reads the program's widest gap; with it, the fp8 control takes
the served tokens' place (``run.py --control``), so ``correct`` has to
come out false.  One JSON line per seed on stdout, and appended to
``--out`` (``bench/out/calibrate.<cell>.jsonl`` by default).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", type=pathlib.Path, default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import run
    from bench.lib import harness

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 1
    harness.setup_compile_cache()
    bench = harness.load_benchmark()
    out = args.out or harness.OUT / f"calibrate.{args.workload}.jsonl"
    out.parent.mkdir(parents=True, exist_ok=True)
    sink = out.open("a")
    for seed in args.seeds:
        t = time.perf_counter()
        res = run.execute(bench, args.workload, seed, args.seconds, False,
                          t, control=args.control)
        line = dict(cell=args.workload, seed=seed, control=args.control,
                    correct=res["correct"], failed=res["failed"],
                    attempted=res["attempted"],
                    run_s=time.perf_counter() - t,
                    **{k: v["value"] for k, v in res["compared"].items()},
                    check=res["check"])
        print(json.dumps(line), flush=True)
        sink.write(json.dumps(line) + "\n")
        sink.flush()
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Serving launcher: a queue of random-prompt requests against one model,
served one request at a time by :class:`repro.serve.engine.ServeEngine`.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-370m \
      --requests 4 --prompt-len 16 --max-new 8
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --full

``--full`` serves the published widths (weights are random, from
``--seed``).  Exits non-zero unless every request ends ``done``.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.launch.compile_cache import setup_compile_cache
from repro.models import model as M
from repro.serve.engine import Request, ServeEngine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = get_config(args.arch) if args.full else get_reduced(args.arch)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    eng = ServeEngine(params, cfg, batch_size=args.requests,
                      max_len=args.prompt_len + args.max_new,
                      seed=args.seed)
    rng = np.random.RandomState(args.seed)
    t0 = time.time()
    for uid in range(args.requests):
        prompt = rng.randint(0, cfg.vocab_size, args.prompt_len)
        eng.submit(Request(uid=uid, prompt=prompt,
                           max_new_tokens=args.max_new,
                           temperature=args.temperature))
    done = eng.run()
    dt = time.time() - t0
    total_new = sum(len(r.generated) for r in done.values())
    for uid, r in sorted(done.items()):
        print(f"req {uid}: {r.status} {r.generated}"
              + (f" ({r.error})" if r.error else ""))
    print(f"{total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s incl. compile)")
    return 0 if all(r.status == "done" for r in done.values()) else 1


if __name__ == "__main__":
    sys.exit(main())

"""Serving example: batched requests, greedy + sampled, across families.

  PYTHONPATH=src python examples/serve_lm.py
  PYTHONPATH=src python examples/serve_lm.py --quantize int8
  PYTHONPATH=src python examples/serve_lm.py --quantize w8a8

``--quantize int8`` demonstrates the weight-quantized serve path:
load (init stands in for a checkpoint restore) -> ``quantize_params``
(every ca_matmul-routed projection becomes an int8 QTensor with fp32
per-channel scales) -> engine startup warmup (the kernel-config registry
plans the ``int8w_*``/dequant-fused variants) -> generate.  The int8
bytes are what streams from HBM; the dequant runs inside the GEMM drain
(see docs/QUANT.md).

``--quantize w8a8`` additionally quantizes activations: the engine runs
a startup calibration pass over sample traffic, attaches static a-scales
to every projection, and serves through the int8xint8 ("ab") kernel —
the MXU's 2x int8 compute rate on top of the byte win
(``int8w_int8a`` cache keys).

``--chaos`` serves a 4-request queue under a deterministic
:class:`repro.runtime.fault.FaultPlan` — one fatal kernel failure (fails
exactly one request), one recoverable kernel failure (re-dispatched on
the XLA oracle, ``gemm.fallback_total``), one NaN decode step (walks the
quant degradation ladder, ``serve.degraded_total``), and one slow decode
step.  It turns the kernel->XLA re-dispatch on, which is off by default.
Statuses print per request; pair with ``--metrics`` to see the fault
counters (see docs/ROBUSTNESS.md).

``--trace trace.jsonl`` writes Chrome-trace-event spans (warmup,
calibration, per-request prefill/decode) — load the file in Perfetto or
chrome://tracing.  ``--metrics`` prints the engine's metrics report
(TTFT/TPOT histograms, prefill/decode split, tokens/s, plan sources) and
enables the GEMM ledger so the report includes achieved-vs-planned
bytes per serve step (see docs/OBSERVABILITY.md).
"""

import argparse

import jax
import numpy as np

from repro.configs import get_reduced
from repro.core import set_gemm_fallback
from repro.models import common as cm
from repro.models import model as M
from repro.obs import enable_tracing, flush
from repro.obs.ledger import get_ledger
from repro.quant import QuantConfig
from repro.runtime.fault import FaultPlan
from repro.serve.engine import Request, ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quantize", choices=["none", "int8", "w8a8"],
                    default="none",
                    help="weight-quantize the serve params (int8 payload, "
                         "fp32 per-channel scales, drain-fused dequant); "
                         "w8a8 additionally calibrates static activation "
                         "scales and serves int8xint8")
    ap.add_argument("--trace", nargs="?", const="trace.jsonl", default=None,
                    metavar="PATH",
                    help="write Perfetto-loadable trace spans to PATH "
                         "(default trace.jsonl)")
    ap.add_argument("--metrics", action="store_true",
                    help="enable the GEMM ledger and print the metrics "
                         "report after serving")
    ap.add_argument("--chaos", action="store_true",
                    help="serve a 4-request queue under a deterministic "
                         "FaultPlan (fatal kernel, recoverable kernel, "
                         "NaN decode step, slow decode step) and print "
                         "per-request statuses; pair with --quantize so "
                         "the NaN triggers the degradation ladder")
    ap.add_argument("--archs", nargs="+",
                    default=["stablelm-1.6b", "mamba2-370m", "zamba2-7b"],
                    help="reduced configs to serve")
    args = ap.parse_args(argv)

    if args.trace:
        print(f"# tracing to {enable_tracing(args.trace)}")
    if args.metrics:
        get_ledger().enable()
    if args.chaos:
        set_gemm_fallback(True)

    for arch in args.archs:
        cfg = get_reduced(arch)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        note = ""
        if args.quantize != "none":
            dense_bytes = sum(int(np.asarray(v).nbytes)
                              for v in params.values())
            params = cm.quantize_params(params, qconfig=QuantConfig())
            q_bytes = sum(v.nbytes if hasattr(v, "nbytes")
                          else int(np.asarray(v).nbytes)
                          for v in params.values())
            note = f" int8w params={q_bytes / 1e6:.2f}MB" \
                   f" ({q_bytes / dense_bytes:.2f}x of dense)"
        eng = ServeEngine(params, cfg, batch_size=2, max_len=40,
                          quantize_activations=(args.quantize == "w8a8"))
        if args.quantize != "none":
            pat = "int8w_int8a" if args.quantize == "w8a8" else "int8w_"
            n_q = sum(1 for k in eng.gemm_plan_sources if pat in k)
            note += f" quant-plans={n_q}"
            if args.quantize == "w8a8":
                note += f" calib-sites={len(eng.calibration_sites)}"
        rng = np.random.RandomState(0)
        if args.chaos:
            # Deterministic chaos: dispatch 0 (request 0's first GEMM) is
            # a fatal kernel failure — exactly that request fails;
            # dispatch 1 (request 1) is recoverable — re-dispatched on
            # the XLA oracle; decode step 5 (request 2's first) goes NaN
            # — the quant ladder degrades and retries; decode step 15
            # (request 3's first) runs slow.
            plan = FaultPlan(kernel_fatal_at=(0,), kernel_fail_at=(1,),
                             nan_decode_at=(5,), slow_decode_at={15: 0.05})
            for uid in range(4):
                eng.submit(Request(uid=uid,
                                   prompt=rng.randint(0, cfg.vocab_size, 12),
                                   max_new_tokens=6))
            with plan:
                done = eng.run()
            stat = " ".join(
                f"req{u}={r.status}"
                + (f"({r.quant_level})" if r.status == "degraded" else "")
                for u, r in sorted(done.items()))
            print(f"{arch:16s} chaos: {stat} "
                  f"injected={sorted(plan.injected)}{note}")
        else:
            for uid in range(2):
                eng.submit(Request(uid=uid,
                                   prompt=rng.randint(0, cfg.vocab_size, 12),
                                   max_new_tokens=6,
                                   temperature=0.0 if uid == 0 else 0.7))
            done = eng.run()
            outs = {u: r.generated for u, r in done.items()}
            print(f"{arch:16s} greedy={outs[0]} sampled={outs[1]}{note}")
        if args.metrics:
            print(f"--- metrics ({arch}) ---")
            print(eng.metrics_report())
    if args.trace:
        flush()
        print(f"# trace written to {args.trace}")


if __name__ == "__main__":
    main()

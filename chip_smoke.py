#!/usr/bin/env python3
"""Chip smoke: serve StableLM-2-1.6B at its published widths on one TPU.

  python chip_smoke.py               # one chip: phases (a), (b), (c)
  python chip_smoke.py --four-chips  # four chips: the multi-chip path only

Weights are random, from ``--seed``; no checkpoint is needed.  Each
phase serves 4 requests (128-token prompts, 16 greedy new tokens)
through :class:`repro.serve.engine.ServeEngine`:

  (a) the default ``"xla"`` GEMM dispatch;
  (b) ``gemm_mode("pallas")``: every projection is a ``ca_gemm_program``;
  (c) (b) plus ``paged_kv=True``: decode attention streams int8 KV pages
      through the paged kernel.

(b) and (c) are compared with (a) on teacher-forced logits (the last
prompt position, then 4 decode steps fed (a)'s tokens).  Greedy token
agreement is printed, not tested: random weights give near-ties.

``--four-chips`` runs only the path that exists across chips, on a
``(1, 4)`` ``("data", "model")`` mesh of the 4 devices: ``dist_matmul``
``ring`` and ``allgather`` at StableLM's FFN shape with local steps under
``"xla"`` and ``"pallas"``, and ``serve/tp.py``'s ``tp_decode_step``,
each against a single-device reference.

Runs in this one process and starts no other.  Exits non-zero, with no
result line, when JAX finds no TPU or when any check fails.  The last
line of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "stablelm-1.6b"
N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 4, 128, 16
FORCED_STEPS = 4

# Logit tolerances, as max |logit - logit_a| over max |logit_a| per row.
# (b) streams the same bf16 operands as (a) and accumulates in fp32, but
# in k-tile order, and rounds its drains (rms prologue, GLU combine,
# residual add) to bf16 at other points than XLA's fused dots: about one
# bf16 rounding (2^-8 relative) per sub-layer, carried through 24
# residual layers, stays within a few percent of the logit range.
TOL_PALLAS = 5e-2
# (c) decode steps read int8 pages with one absmax scale per page: an
# error of about absmax / (127 * sqrt(12)), ~1% of each K/V page's range,
# enters every decode step's attention in all 24 layers.  Its prefill
# attends over unquantized K/V, so its prefill row is held to TOL_PALLAS.
TOL_INT8_KV = 1e-1
# Four chips: the distributed GEMMs accumulate bf16 products in fp32 like
# the single-device dot and differ only in summation order.
TOL_DIST = 1e-3
# The TP block is fp32 on both sides; the MXU rounds fp32 operands to
# bf16 passes at default precision on both, which then differ in where
# the ring splits k and in the order of the sums.
TOL_TP = 1e-2

PHASES = (("a", "xla", False), ("b", "pallas", False), ("c", "pallas", True))


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


class CacheEvents:
    """Counts JAX persistent compilation cache hits and misses."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _counter(snapshot: dict, name: str) -> float:
    return snapshot.get(name, {}).get("value", 0)


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def rel_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per-row max |got - want| over max |want|."""
    got = np.asarray(got, np.float64).reshape(len(want), -1)
    want = np.asarray(want, np.float64).reshape(len(want), -1)
    return np.abs(got - want).max(axis=1) / np.abs(want).max(axis=1)


def serve_phase(name, mode, paged, params, cfg, prompts, warm_prompt,
                forced, seed, cache_info, events):
    """Serve one phase through ServeEngine and print its report line.

    Returns (teacher-forced logits, forced tokens, generated tokens,
    errors); ``forced=None`` forces this phase's own first tokens.
    """
    from repro.core.gemm import gemm_mode
    from repro.obs import get_metrics, reset_metrics
    from repro.obs.ledger import (AttnRecord, GemmLedger, GemmRecord,
                                  get_ledger, set_ledger)
    from repro.serve.engine import Request, ServeEngine

    set_ledger(GemmLedger(enabled=True))
    reset_metrics()
    hits0, misses0 = events.hits, events.misses
    errors = []
    with gemm_mode(mode):
        t0 = time.perf_counter()
        eng = ServeEngine(params, cfg, batch_size=len(prompts),
                          max_len=PROMPT_LEN + NEW_TOKENS, seed=seed,
                          paged_kv=paged)
        warm_uid = len(prompts)
        eng.submit(Request(uid=warm_uid, prompt=warm_prompt,
                           max_new_tokens=NEW_TOKENS))
        warm = eng.run()[warm_uid]
        compile_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        for uid, prompt in enumerate(prompts):
            eng.submit(Request(uid=uid, prompt=prompt,
                               max_new_tokens=NEW_TOKENS))
        done = eng.run()
        serve_s = time.perf_counter() - t1
        reqs = [done[uid] for uid in range(len(prompts))]
        if forced is None:
            forced = reqs[0].generated[:FORCED_STEPS]
        logits = eng.teacher_forced_logits(prompts[0], forced)
    snap = get_metrics().snapshot()
    records = get_ledger().records
    pallas_gemms = sum(1 for r in records
                       if isinstance(r, GemmRecord) and r.mode == "pallas")
    pallas_attn = sum(1 for r in records
                      if isinstance(r, AttnRecord) and r.mode == "pallas")
    statuses = [r.status for r in [warm] + reqs]
    for r in [warm] + reqs:
        if r.status != "done":
            errors.append(f"phase {name}: request {r.uid} ended "
                          f"{r.status}: {r.error}")
    fallback = _counter(snap, "gemm.fallback_total")
    degraded = _counter(snap, "serve.degraded_total")
    if fallback or degraded:
        errors.append(f"phase {name}: gemm.fallback_total={fallback} "
                      f"serve.degraded_total={degraded}")
    if mode == "pallas" and not pallas_gemms:
        errors.append(f"phase {name}: no Pallas-mode GEMM in the ledger")
    if mode == "pallas" and paged and not pallas_attn:
        errors.append(f"phase {name}: no paged-kernel attention in the "
                      f"ledger")
    if not np.all(np.isfinite(logits)):
        errors.append(f"phase {name}: non-finite teacher-forced logits")
    tokens = sum(len(r.generated) for r in reqs)
    report = {
        "phase": name, "dispatch": mode, "paged_kv": paged,
        "compile_warmup_s": compile_s, "serve_s": serve_s,
        "tokens": tokens, "tokens_per_s": tokens / serve_s,
        "statuses": statuses, "pallas_gemm_records": pallas_gemms,
        "pallas_attn_records": pallas_attn,
        "gemm_fallback_total": fallback, "serve_degraded_total": degraded,
        "compile_cache_dir": cache_info[0],
        "compile_cache_from_env": cache_info[1],
        "compile_cache_hits": events.hits - hits0,
        "compile_cache_misses": events.misses - misses0,
        "device_kind": jax.devices()[0].device_kind,
        "peak_bytes_in_use": _peak_bytes(),
    }
    print("phase " + json.dumps(report), flush=True)
    generated = [r.generated for r in reqs]
    del eng
    gc.collect()
    return logits, forced, generated, errors


def one_chip(seed: int, cache_info, events) -> int:
    from repro.configs import get_config
    from repro.models import model as M

    cfg = get_config(ARCH)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size, PROMPT_LEN)
               for _ in range(N_REQUESTS)]
    warm_prompt = rng.randint(0, cfg.vocab_size, PROMPT_LEN)
    print(f"model {ARCH}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} params={cfg.n_params()}", flush=True)

    errors, logits, generated, forced = [], {}, {}, None
    for name, mode, paged in PHASES:
        logits[name], forced, generated[name], errs = serve_phase(
            name, mode, paged, params, cfg, prompts, warm_prompt, forced,
            seed, cache_info, events)
        errors += errs

    for name, decode_tol in (("b", TOL_PALLAS), ("c", TOL_INT8_KV)):
        err = rel_err(logits[name], logits["a"])
        prefill_err, decode_err = float(err[0]), float(err[1:].max())
        top1 = float(np.mean(np.argmax(logits[name], 1)
                             == np.argmax(logits["a"], 1)))
        same = [g == h for g, h in zip(generated[name], generated["a"])]
        agree = float(np.mean([t == u for g, h in zip(generated[name],
                                                      generated["a"])
                               for t, u in zip(g, h)]))
        print("compare " + json.dumps({
            "phase": name, "vs": "a",
            "prefill_rel_err": prefill_err, "prefill_tol": TOL_PALLAS,
            "decode_rel_err": decode_err, "decode_tol": decode_tol,
            "forced_top1_agreement": top1,
            "greedy_token_agreement": agree,
            "greedy_requests_identical": sum(same)}), flush=True)
        if not prefill_err <= TOL_PALLAS:
            errors.append(f"phase {name}: prefill logits rel err "
                          f"{prefill_err} > {TOL_PALLAS}")
        if not decode_err <= decode_tol:
            errors.append(f"phase {name}: decode logits rel err "
                          f"{decode_err} > {decode_tol}")
    for e in errors:
        _fail(e)
    return 1 if errors else 0


def four_chips(seed: int) -> int:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import dist_matmul
    from repro.core.gemm import gemm_mode
    from repro.launch.mesh import make_mesh
    from repro.serve import tp

    devices = jax.devices()
    if len(devices) != 4:
        return _fail(f"--four-chips needs 4 devices, found {len(devices)}")
    mesh = make_mesh((1, 4), ("data", "model"))
    errors = []

    def n_devices(x) -> int:
        return len({s.device for s in x.addressable_shards})

    def n_pieces(x) -> int:
        return len({str(s.index) for s in x.addressable_shards})

    # dist_matmul at StableLM's FFN shape, each schedule x local dispatch.
    m, k, n = 2048, 2048, 5632
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, (m, k), jnp.float32).astype(jnp.bfloat16)
    b = (jax.random.normal(kb, (k, n), jnp.float32)
         / np.sqrt(k)).astype(jnp.bfloat16)
    a = jax.device_put(a, NamedSharding(mesh, P("data", "model")))
    b = jax.device_put(b, NamedSharding(mesh, P(None, "model")))
    ref = jnp.dot(jax.device_put(a, devices[0]), jax.device_put(b, devices[0]),
                  preferred_element_type=jnp.float32)
    for schedule in ("ring", "allgather"):
        for mode in ("xla", "pallas"):
            with gemm_mode(mode):
                f = jax.jit(lambda x, y, s=schedule: dist_matmul(
                    x, y, mesh, schedule=s, out_dtype=jnp.float32))
                kernel = "tpu_custom_call" in f.lower(a, b).as_text()
                t0 = time.perf_counter()
                c = jax.block_until_ready(f(a, b))
                first_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                c = jax.block_until_ready(f(a, b))
                second_s = time.perf_counter() - t0
            err = float(rel_err(c.reshape(1, -1), ref.reshape(1, -1))[0])
            row = {"case": "dist_matmul", "schedule": schedule,
                   "local_dispatch": mode, "m": m, "k": k, "n": n,
                   "rel_err": err, "tol": TOL_DIST,
                   "pallas_kernel_in_program": kernel,
                   "out_devices": n_devices(c), "out_pieces": n_pieces(c),
                   "first_call_s": first_s, "second_call_s": second_s}
            print("four " + json.dumps(row), flush=True)
            if not (err <= TOL_DIST and n_devices(c) == 4
                    and n_pieces(c) == 4 and kernel == (mode == "pallas")):
                errors.append(f"dist_matmul {schedule}/{mode}: {row}")
    w_devs = (n_devices(b), n_pieces(b))
    print("four " + json.dumps({"case": "weight_shards", "devices": w_devs[0],
                                "pieces": w_devs[1]}), flush=True)
    if w_devs != (4, 4):
        errors.append(f"weight shards on {w_devs} devices/pieces")

    # serve/tp.py's decode block at StableLM widths, batch 8.
    cfg = tp.TpDecodeConfig(d_model=2048, n_heads=32, d_ff=5632)
    params = tp.init_tp_params(cfg, jax.random.PRNGKey(seed + 1))
    placed = tp.place_tp_params(params, cfg, mesh)
    rng = np.random.RandomState(seed)
    kv = kv_ref = None
    worst = 0.0
    for _ in range(3):
        x = jnp.asarray(rng.randn(8, cfg.d_model) * 0.1, jnp.float32)
        y, kv = tp.tp_decode_step(placed, x, kv, cfg, mesh)
        y_ref, kv_ref = tp.tp_decode_reference(params, x, kv_ref, cfg)
        worst = max(worst, float(rel_err(y.reshape(1, -1),
                                         y_ref.reshape(1, -1))[0]))
    sharded = {name: (n_devices(w), n_pieces(w))
               for name, w in placed.items() if w.ndim == 2}
    row = {"case": "tp_decode_step", "d_model": cfg.d_model,
           "heads": cfg.n_heads, "d_ff": cfg.d_ff, "batch": 8, "steps": 3,
           "rel_err": worst, "tol": TOL_TP, "out_devices": n_devices(y),
           "weight_shards": sharded}
    print("four " + json.dumps(row), flush=True)
    if not (worst <= TOL_TP and n_devices(y) == 4
            and all(v == (4, 4) for v in sharded.values())):
        errors.append(f"tp_decode_step: {row}")
    for e in errors:
        _fail(e)
    return 1 if errors else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip path, on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.hardware import target_for_device
    from repro.launch.compile_cache import setup_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return _fail(f"no TPU: JAX's first device is {dev.platform!r}")
    hw = target_for_device(dev)   # an unknown chip fails here
    cache_info = setup_compile_cache()
    events = CacheEvents()
    print(f"device {dev.device_kind} x{len(jax.devices())} target={hw.name} "
          f"peak_bf16={hw.peak_flops_bf16} hbm_bw={hw.hbm_bandwidth} "
          f"compile_cache={cache_info[0]} from_env={cache_info[1]}",
          flush=True)
    rc = (four_chips(args.seed) if args.four_chips
          else one_chip(args.seed, cache_info, events))
    if rc:
        return rc
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

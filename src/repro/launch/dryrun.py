import os
# A CPU rehearsal by design: 512 forced host devices stand in for the
# production mesh, and the platform is pinned so it never takes a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs the step function for the shape's kind
     (train_4k -> train_step, prefill_32k -> prefill, decode_* -> serve_step),
  3. ``jax.jit(...).lower(**input_specs).compile()`` under the mesh +
     activation-sharding policy,
  4. records memory_analysis(), cost_analysis(), and the trip-count-aware
     HLO walk (flops / bytes / collective bytes per device) to a JSON
     artifact in experiments/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--jobs N]
"""

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (SHAPES, applicable_shapes, get_config,  # noqa: E402
                           list_archs)
from repro.configs.base import ModelConfig, ShapeConfig  # noqa: E402
from repro.launch import hlo_analysis as H  # noqa: E402
from repro.launch import specs as S  # noqa: E402
from repro.launch.mesh import batch_axes, make_production_mesh, n_chips  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.sharding.rules import activation_sharding  # noqa: E402
from repro.train import step as train_mod  # noqa: E402

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "experiments", "dryrun")

# Serving weights only FSDP-shard when TP alone does not fit HBM.
SERVE_FSDP = {"qwen2-vl-72b"}

# Per-arch microbatch counts for train_4k (activation-footprint tuning;
# EXPERIMENTS §Perf).  Default 8.
TRAIN_MICROBATCHES = {"zamba2-7b": 16}


def _mem_dict(ma) -> Dict:
    return {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "generated_code_bytes": ma.generated_code_size_in_bytes,
    }


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               cfg_override: Optional[ModelConfig] = None,
               return_compiled: bool = False,
               microbatches: Optional[int] = None,
               weight_hoist: bool = False, seq_parallel: bool = False):
    """Lower+compile one cell; returns the artifact dict."""
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    if microbatches is None:
        microbatches = TRAIN_MICROBATCHES.get(arch, 8)
    # The strided microbatch split needs (B/microbatches) divisible by the
    # batch-sharding degree, or GSPMD replicates the whole batch (found
    # the hard way: zamba2 2x16x16 at mb=16 -> 147 GiB).
    total_shards = 1
    for a in batch_axes(mesh := make_production_mesh(multi_pod=multi_pod)):
        total_shards *= mesh.shape[a]
    max_mb = max(1, shape.global_batch // total_shards)
    microbatches = min(microbatches, max_mb)
    t0 = time.time()

    with mesh, activation_sharding(
            mesh, batch_axes(mesh),
            seq_axis="model" if seq_parallel else None):
        if shape.kind == "train":
            state_sds, state_sh = S.state_inputs(cfg, mesh, fsdp=True)
            batch_sds, batch_sh = S.train_inputs(cfg, shape, mesh)
            reshard = None
            reshard_g = None
            if weight_hoist:
                # Perf iteration #3: hoist a single bf16 TP-only gather of
                # the weights out of the microbatch scan (see train/step).
                from repro.models.model import model_defs
                from repro.sharding.rules import pspecs_for_defs
                tp_specs = pspecs_for_defs(model_defs(cfg), mesh, fsdp=False)
                tp_sh = {k: jax.sharding.NamedSharding(mesh, v)
                         for k, v in tp_specs.items()}

                def reshard(tree):
                    return {k: jax.lax.with_sharding_constraint(v, tp_sh[k])
                            for k, v in tree.items()}

                fsdp_specs = pspecs_for_defs(model_defs(cfg), mesh,
                                             fsdp=True,
                                             fsdp_axes=batch_axes(mesh))
                fsdp_sh = {k: jax.sharding.NamedSharding(mesh, v)
                           for k, v in fsdp_specs.items()}

                def reshard_g(tree):
                    return {k: jax.lax.with_sharding_constraint(v, fsdp_sh[k])
                            for k, v in tree.items()}
            else:
                reshard_g = None
            step_fn = train_mod.build_train_step(
                cfg, microbatches=microbatches, reshard_params=reshard,
                reshard_grads=reshard_g if weight_hoist else None)
            lowered = jax.jit(
                step_fn,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            ).lower(state_sds, batch_sds)
        elif shape.kind == "prefill":
            params_sds, params_sh = S.serve_param_inputs(
                cfg, mesh, fsdp=arch in SERVE_FSDP)
            in_sds, in_sh = S.prefill_inputs(cfg, shape, mesh)

            cache_sds, cache_sh = S.cache_inputs(cfg, shape, mesh)

            def prefill_fn(params, batch):
                # Serving keeps only the last position's logits (the full
                # (B, 32k, V) logits tensor is sampling-irrelevant and
                # would dominate memory).
                logits, cache = M.prefill(params, batch, cfg,
                                          max_len=shape.seq_len)
                return logits[:, -1:], cache

            lowered = jax.jit(
                prefill_fn, in_shardings=(params_sh, in_sh),
                out_shardings=(None, cache_sh),
            ).lower(params_sds, in_sds)
        else:  # decode
            params_sds, params_sh = S.serve_param_inputs(
                cfg, mesh, fsdp=arch in SERVE_FSDP)
            tok_sds, tok_sh = S.decode_token_inputs(cfg, shape, mesh)
            cache_sds, cache_sh = S.cache_inputs(cfg, shape, mesh)

            def serve_step(params, token_in, cache, step):
                return M.decode_step(params, token_in, cache, step, cfg)

            lowered = jax.jit(
                serve_step,
                in_shardings=(params_sh, tok_sh, cache_sh, None),
                out_shardings=(None, cache_sh),
                donate_argnums=(2,),
            ).lower(params_sds, tok_sds, cache_sds,
                    jax.ShapeDtypeStruct((), jnp.int32))

        compiled = lowered.compile()

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    hlo_cost = H.analyze_hlo_text(compiled.as_text())
    art = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": n_chips(mesh),
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "compile_s": round(time.time() - t0, 1),
        "memory": _mem_dict(ma),
        "xla_cost_analysis": {k: ca.get(k) for k in
                              ("flops", "bytes accessed")},
        "hlo": H.summarize(hlo_cost),
        "n_params": cfg.n_params(),
        "n_active_params": cfg.active_params(),
    }
    if return_compiled:
        return art, compiled
    return art


def run_cells(cells, multi_pod: bool, out_dir: str) -> int:
    os.makedirs(out_dir, exist_ok=True)
    failures = 0
    for arch, shape_name in cells:
        tag = f"{arch}__{shape_name}__{'2x16x16' if multi_pod else '16x16'}"
        out_path = os.path.join(out_dir, tag + ".json")
        try:
            art = lower_cell(arch, shape_name, multi_pod)
            with open(out_path, "w") as f:
                json.dump(art, f, indent=1)
            # A cell that failed in an earlier run leaves a .err next to
            # the artifact; a later success supersedes it — drop it so
            # the artifact dir reflects current state only.
            err_path = out_path + ".err"
            if os.path.exists(err_path):
                os.remove(err_path)
            mem_gb = (art["memory"]["argument_bytes"]
                      + art["memory"]["temp_bytes"]) / 2 ** 30
            print(f"OK   {tag}  compile={art['compile_s']}s "
                  f"mem/dev={mem_gb:.2f}GiB "
                  f"flops/dev={art['hlo']['flops_per_device']:.3e} "
                  f"coll/dev={art['hlo']['collective_bytes_per_device']:.3e}",
                  flush=True)
        except Exception as e:  # repro: noqa RPR004 -- sweep isolation: record the cell's failure and continue
            failures += 1
            with open(out_path + ".err", "w") as f:
                f.write(traceback.format_exc())
            print(f"FAIL {tag}  {type(e).__name__}: {str(e)[:200]}",
                  flush=True)
    return failures


def all_cells():
    cells = []
    for arch in list_archs():
        cfg = get_config(arch)
        for shape_name in applicable_shapes(cfg):
            cells.append((arch, shape_name))
    return cells


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--shard-index", type=int, default=0,
                    help="process this cell subset (round-robin)")
    ap.add_argument("--shard-count", type=int, default=1)
    args = ap.parse_args(argv)

    if args.all:
        cells = all_cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    cells = [c for i, c in enumerate(cells)
             if i % args.shard_count == args.shard_index]
    print(f"dry-run: {len(cells)} cells on "
          f"{'2x16x16' if args.multi_pod else '16x16'} "
          f"({len(jax.devices())} host devices)", flush=True)
    return run_cells(cells, args.multi_pod, args.out)


if __name__ == "__main__":
    sys.exit(main())

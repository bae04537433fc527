"""Training launcher: data pipeline + train step + checkpoints + fault
supervision, per-arch config selection.

On this CPU container it runs reduced configs end-to-end (used by
examples/train_lm.py); on a real TPU fleet the same driver runs the full
configs on the production mesh (--full --multi-pod).

  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
      --steps 100 --ckpt-dir /tmp/ckpt [--microbatches 4] [--resume]
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config, get_reduced
from repro.data.pipeline import DataConfig, batch_for_model
from repro.launch.compile_cache import setup_compile_cache
from repro.obs import get_metrics, span
from repro.optim import adamw
from repro.runtime.fault import HeartbeatMonitor
from repro.train import step as T


def run_training(
    arch: str,
    steps: int,
    *,
    full: bool = False,
    seq_len: int = 64,
    global_batch: int = 8,
    microbatches: int = 1,
    lr: float = 1e-3,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 25,
    resume: bool = False,
    seed: int = 0,
    log_every: int = 10,
    fail_at: Optional[int] = None,
):
    cfg = get_config(arch) if full else get_reduced(arch)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                          global_batch=global_batch, seed=seed)
    opt_cfg = adamw.AdamWConfig(lr=lr, warmup_steps=max(steps // 20, 1),
                                total_steps=steps)
    step_fn = jax.jit(T.build_train_step(cfg, opt_cfg,
                                         microbatches=microbatches))
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    mon = HeartbeatMonitor(n_hosts=1)

    state = T.init_state(cfg, jax.random.PRNGKey(seed))
    start = 0
    if resume and mgr is not None and mgr.latest_step() is not None:
        state = mgr.restore(state)
        start = int(state.step)
        print(f"resumed from checkpoint at step {start}")

    losses = []
    obs = get_metrics()
    step_hist = obs.histogram("train.step_seconds",
                              "Wall time of one optimizer step")
    steps_done = obs.counter("train.steps_total", "Optimizer steps run")
    loss_gauge = obs.gauge("train.loss", "Most recent training loss")
    t0 = time.time()
    for i in range(start, steps):
        t_step = time.perf_counter()
        batch = batch_for_model(cfg, data_cfg, i)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        with span("train.step", step=i, arch=arch):
            state, metrics = step_fn(state, batch)
            jax.block_until_ready(metrics["loss"])
        mon.beat(0, i)
        losses.append(float(metrics["loss"]))
        step_hist.observe(time.perf_counter() - t_step)
        steps_done.inc()
        loss_gauge.set(losses[-1])
        obs.gauge("train.tokens_per_second",
                  "Throughput of the last optimizer step").set(
                      data_cfg.global_batch * data_cfg.seq_len
                      / max(time.perf_counter() - t_step, 1e-9))
        if fail_at is not None and i == fail_at:
            raise RuntimeError(f"injected failure at step {i}")
        if mgr is not None and (i + 1) % ckpt_every == 0:
            mgr.save_async(i, state)
        if (i + 1) % log_every == 0 or i == start:
            dt = (time.time() - t0) / max(i - start + 1, 1)
            print(f"step {i+1:5d}  loss {losses[-1]:.4f}  "
                  f"lr {float(metrics['lr']):.2e}  "
                  f"gnorm {float(metrics['grad_norm']):.2f}  "
                  f"{dt*1e3:.0f} ms/step", flush=True)
    if mgr is not None:
        mgr.save(steps - 1, state)
        mgr.wait()
    return state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--full", action="store_true",
                    help="published config (requires real accelerators)")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a crash at this step (fault-tolerance demo)")
    args = ap.parse_args()
    setup_compile_cache()
    _, losses = run_training(
        args.arch, args.steps, full=args.full, seq_len=args.seq_len,
        global_batch=args.global_batch, microbatches=args.microbatches,
        lr=args.lr, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        resume=args.resume, fail_at=args.fail_at)
    print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")


if __name__ == "__main__":
    main()

"""Training launcher: end-to-end driver with checkpoint/restart.

On this CPU container it trains *reduced* configs for real (the
``--full`` flag selects the production config for use on an actual pod).
Fault tolerance is wired in: heartbeat thread, step watchdog (straggler
log), periodic async checkpoints, and crash-restart through
``runtime.fault.restart_loop`` (``--simulate-failure-at N`` injects one).

Example::

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
        --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt as ckpt_lib
from repro.configs import get_config
from repro.data.pipeline import make_data
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.parallel.sharding import make_plan
from repro.runtime.fault import (Heartbeat, SimulatedFailure, StepWatchdog,
                                 restart_loop)
from repro.train.optimizer import OptimizerConfig
from repro.train.trainer import (init_training, make_train_step,
                                 split_microbatches)


def train(args) -> int:
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    if args.precision:
        cfg = dataclasses.replace(cfg, matmul_precision=args.precision)

    n_dev = len(jax.devices())
    model_par = min(args.model_parallel, n_dev)
    mesh = make_local_mesh(data=n_dev // model_par, model=model_par)
    oc = OptimizerConfig(peak_lr=args.lr, warmup_steps=args.warmup,
                         total_steps=args.steps)
    data = make_data(cfg, args.seq, args.batch, seed=args.data_seed)

    def run(resume) -> int:
        params, axes, opt_state = init_training(cfg, jax.random.key(args.seed))
        plan = make_plan(cfg, axes, mesh, kind="train")
        step_fn = make_train_step(cfg, oc, plan, grad_accum=args.grad_accum)

        start = 0
        if resume is not None:
            latest = ckpt_lib.latest_step(args.ckpt_dir)
            if latest is not None:
                tree = {"params": params, "opt": opt_state}
                tree = ckpt_lib.restore(args.ckpt_dir, latest, tree)
                params, opt_state = tree["params"], tree["opt"]
                start = ckpt_lib.load_manifest(
                    args.ckpt_dir, latest)["meta"]["data_cursor"]
                print(f"[train] restored step {latest}, "
                      f"data cursor {start}", flush=True)

        hb = Heartbeat(os.path.join(args.ckpt_dir, "heartbeat.json")).start()
        wd = StepWatchdog()
        pending = None
        try:
            for step in range(start, args.steps):
                if args.simulate_failure_at == step and resume is None:
                    raise SimulatedFailure(f"injected at step {step}")
                wd.start_step(step)
                batch = data.batch_at(step)
                if args.grad_accum > 1:
                    batch = split_microbatches(batch, args.grad_accum)
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                params, opt_state, metrics = step_fn(params, opt_state,
                                                     batch)
                hb.step = step
                ev = wd.end_step()
                if ev:
                    print(f"[watchdog] straggler step {ev.step}: "
                          f"{ev.duration_s:.2f}s vs median "
                          f"{ev.median_s:.2f}s", flush=True)
                if step % args.log_every == 0 or step == args.steps - 1:
                    loss = float(metrics["loss"])
                    print(f"step {step:5d} loss {loss:.4f} "
                          f"lr {float(metrics['lr']):.2e} "
                          f"gnorm {float(metrics['grad_norm']):.2f}",
                          flush=True)
                if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                    if pending is not None:
                        pending.join()
                    pending = ckpt_lib.save(
                        args.ckpt_dir, step + 1,
                        {"params": params, "opt": opt_state},
                        meta={"data_cursor": step + 1,
                              "arch": cfg.name},
                        async_write=True)
            if pending is not None:
                pending.join()
            ckpt_lib.save(args.ckpt_dir, args.steps,
                          {"params": params, "opt": opt_state},
                          meta={"data_cursor": args.steps,
                                "arch": cfg.name})
            return args.steps
        finally:
            hb.stop()

    final = restart_loop(run, max_restarts=args.max_restarts,
                         on_restart=lambda i, e: print(
                             f"[restart {i}] {e}", flush=True))
    print(f"[train] done at step {final}")
    return final


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--full", action="store_true",
                    help="production config (pods); default: reduced")
    ap.add_argument("--precision", default=None,
                    choices=["bf16", "int8_quant", "ozaki_fp64"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-seed", type=int, default=1234)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument("--simulate-failure-at", type=int, default=-1)
    args = ap.parse_args()
    enable_compile_cache()
    train(args)


if __name__ == "__main__":
    main()

"""End-to-end training driver with fault-tolerant checkpointing.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Resume is automatic: if the checkpoint dir holds a committed step, training
continues from it (deterministic data makes the stream seamless).  On a real
cluster this script runs per host under the launcher; here it drives the
single-process mesh.  ``--simulate-failure N`` exits hard at step N to
exercise the restart path (see tests/test_train_e2e.py).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import numpy as np

from repro.configs.base import get_config, reduced
from repro.distributed.fault import StragglerWatchdog
from repro.train import checkpoint as ckpt_lib
from repro.train import train_loop
from repro.train.data import DataConfig, TokenStream
from repro.train.optimizer import OptConfig


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--simulate-failure", type=int, default=-1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--obs-dir", default="",
                    help="record step-time metrics (and trace) run record")
    ap.add_argument("--trace", action="store_true",
                    help="capture per-step Chrome trace events")
    args = ap.parse_args(argv)

    if args.obs_dir or args.trace:
        import repro.obs as obs

        obs.enable()
        if args.trace:
            obs.install_tracer()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    tc = train_loop.TrainConfig(
        accum_steps=args.accum,
        compress_grads=args.compress_grads,
        opt=OptConfig(lr=args.lr, warmup_steps=5, total_steps=args.steps),
    )
    step_fn = jax.jit(train_loop.make_train_step(cfg, tc), donate_argnums=0)
    step_fn = train_loop.instrument_step(
        step_fn, tokens_per_step=args.batch * args.seq * max(args.accum, 1)
    )
    stream = TokenStream(cfg, args.batch, args.seq, DataConfig())

    start = 0
    state = train_loop.init_state(cfg, jax.random.PRNGKey(0))
    if args.ckpt_dir:
        latest = ckpt_lib.latest_step(args.ckpt_dir)
        if latest is not None and ckpt_lib.verify(args.ckpt_dir, latest):
            state = ckpt_lib.restore(args.ckpt_dir, latest, state)
            start = latest
            print(f"resumed from step {latest}")

    watchdog = StragglerWatchdog(n_hosts=1)
    losses = []
    for step in range(start, args.steps):
        t0 = time.time()
        batch = {k: jax.numpy.asarray(v) for k, v in stream.batch_at(step).items()}
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        watchdog.observe(0, time.time() - t0)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step}: loss {loss:.4f} "
                  f"({time.time()-t0:.2f}s)")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt_lib.save(args.ckpt_dir, step + 1, state)
        if args.simulate_failure == step:
            print("simulating hard failure", file=sys.stderr)
            os._exit(17)
    if args.obs_dir:
        from repro.obs.recorder import record_run

        path = record_run(
            args.obs_dir,
            meta={
                "layer": "train", "arch": args.arch, "steps": args.steps,
                "batch": args.batch, "seq": args.seq, "accum": args.accum,
            },
        )
        print(f"run record -> {path}")
    return {"losses": losses, "final_step": args.steps}


if __name__ == "__main__":
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    main()

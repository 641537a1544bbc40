"""End-to-end training driver.

Runs on the first device JAX finds and names it in its last line (the
production mesh path is exercised by dryrun.py). Integrates: synthetic data
pipeline, AdamW (+WSD for minicpm), microbatching, checkpoint/restart
through the paper's cache designs, and crash recovery.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b-smoke \
        --steps 50 --ckpt-design log --ckpt-every 10
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import SyntheticLMDataset, make_batch_iterator
from repro.launch.compile_cache import enable_compilation_cache
from repro.models import build_model
from repro.training import AdamWConfig, init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b-smoke")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-design", choices=("log", "paged"), default="log")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compilation_cache()
    cfg = get_config(args.arch)
    model = build_model(cfg, remat=True)
    opt_cfg = AdamWConfig(lr=args.lr, schedule=cfg.lr_schedule,
                          warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps)
    state = init_train_state(model, jax.random.PRNGKey(args.seed))
    step_fn = jax.jit(make_train_step(model, opt_cfg,
                                      microbatches=args.microbatches))

    ds = SyntheticLMDataset(cfg.vocab_size, args.seq, args.batch,
                            seed=args.seed)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"loss_floor≈{ds.entropy_floor:.3f}")

    mgr = None
    start_step = 0
    if args.ckpt_every:
        mgr = CheckpointManager(args.ckpt_design)
        if args.resume:
            start_step, state = mgr.restore(state)
            print(f"resumed at step {start_step}")

    it = make_batch_iterator(ds, start_step, args.microbatches)
    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        state, metrics = step_fn(state, batch)
        if step % max(args.steps // 10, 1) == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.3f}")
        if mgr is not None and (step + 1) % args.ckpt_every == 0:
            t = mgr.save(step + 1, state)
            print(f"  ckpt[{args.ckpt_design}] step {step+1} "
                  f"sim_save={t*1e3:.2f}ms")
    dt = time.time() - t0
    tokens = args.steps * args.batch * args.seq
    dev = jax.devices()[0]
    print(f"done: {dt:.1f}s wall, {tokens/dt:.0f} tok/s "
          f"({dev.platform} {dev.device_kind} x{len(jax.devices())})")
    return float(metrics["loss"])


if __name__ == "__main__":
    main()

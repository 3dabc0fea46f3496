"""Training driver: synthetic-data LM training with checkpointing,
auto-resume, and straggler watchdog.

Single-host by default (CPU-runnable with reduced configs); on a real
cluster the same driver runs under ``jax.distributed`` with the
production mesh — see launch/dryrun.py for the mesh/sharding wiring.

Example (CPU, ~100M-param model, a few hundred steps):
    PYTHONPATH=src python -m repro.launch.train \
        --arch llama3.2-3b --reduce --d-model 512 --layers 12 \
        --steps 300 --batch 16 --seq 256
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.configs import reduce_for_smoke
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.train import AdamWConfig
from repro.train import StepTimer
from repro.train import StepWatchdog
from repro.train import init_train_state
from repro.train import make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--reduce", action="store_true",
                    help="smoke-reduced config (CPU-sized)")
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduce:
        cfg = reduce_for_smoke(cfg)
    changes = {}
    if args.d_model:
        changes.update(d_model=args.d_model,
                       d_ff=4 * args.d_model if cfg.d_ff else 0,
                       head_dim=args.d_model // max(cfg.n_heads, 1)
                       if cfg.n_heads else 0)
    if args.layers:
        changes["n_layers"] = args.layers
    if changes:
        cfg = dataclasses.replace(cfg, **changes)

    params = init_params(cfg, jax.random.key(0))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params / 1e6:.1f}M "
          f"layers={cfg.n_layers} d={cfg.d_model}")

    state = init_train_state(params)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=min(50, args.steps // 10),
                          total_steps=args.steps)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg,
                                      microbatches=args.microbatches),
                      donate_argnums=(0,))
    data = SyntheticLM(cfg.vocab, args.seq, args.batch)

    start_step = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        restored = mgr.restore_latest(state)
        if restored is not None:
            start_step, state = restored
            print(f"resumed from step {start_step}")

    watchdog = StepWatchdog(
        on_straggler=lambda s, d: print(
            f"[watchdog] step {s}: {d:.2f}s — straggler policy engaged "
            f"(log/alert; evict+elastic-restart on real cluster)"))

    t_start = time.time()
    for step in range(start_step, args.steps):
        batch = jnp.asarray(data.batch(step))
        with StepTimer() as t:
            state, metrics = step_fn(state, batch)
            jax.block_until_ready(metrics["loss"])
        watchdog.record(step, t.elapsed)
        if step % args.log_every == 0 or step == args.steps - 1:
            tok_s = args.batch * args.seq / t.elapsed
            print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.2f} "
                  f"{t.elapsed * 1e3:.0f}ms {tok_s:.0f} tok/s")
        if mgr and step and step % args.ckpt_every == 0:
            mgr.save(step, state)
    if mgr:
        mgr.save(args.steps, state)
    print(f"done in {time.time() - t_start:.1f}s "
          f"(stragglers flagged: {len(watchdog.flagged_steps)})")


if __name__ == "__main__":
    main()

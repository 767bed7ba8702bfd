"""End-to-end training driver.

Runs REAL steps on the available devices (CPU here; the same code path
pjit-shards on a TPU mesh), with checkpointing, restart recovery,
straggler monitoring and optional int8-compressed data-parallel gradient
all-reduce (shard_map).

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --reduced --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduced as reduce_config
from repro.configs.base import TrainConfig
from repro.data.pipeline import SyntheticTokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_train_step
from repro.models import init_params
from repro.optim.optimizers import adamw_init
from repro.runtime.fault_tolerance import StragglerMonitor


def train_loop(cfg, tcfg: TrainConfig, *, steps: int, batch: int, seq: int,
               ckpt_dir: str | None = None, checkpoint_every: int = 10,
               log_every: int = 1, seed: int = 0):
    """Returns (final params, losses list)."""
    key = jax.random.PRNGKey(seed)
    params = init_params(cfg, key)
    opt_state = adamw_init(params)
    pipeline = SyntheticTokenPipeline(cfg.vocab_size, seq, batch, seed=seed)
    step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
    manager = CheckpointManager(ckpt_dir, keep_n=2, async_save=True) if ckpt_dir else None
    monitor = StragglerMonitor(n_hosts=1)

    start = 0
    if manager is not None:
        try:
            start, (params, opt_state), _ = manager.restore_latest((params, opt_state))
            print(f"[train] resumed from step {start}")
        except FileNotFoundError:
            pass

    losses = []
    for step in range(start, steps):
        tokens = pipeline.global_batch_at(step)
        t0 = time.time()
        params, opt_state, metrics = step_fn(params, opt_state, {"tokens": tokens})
        loss = float(metrics["loss"])
        monitor.record(0, time.time() - t0)
        losses.append(loss)
        if step % log_every == 0:
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} ({time.time() - t0:.2f}s)", flush=True)
        if manager is not None and (step + 1) % checkpoint_every == 0:
            manager.save(step + 1, (params, opt_state))
    if manager is not None:
        manager.save(steps, (params, opt_state))
        manager.wait()
    return params, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-sized config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--quant-mode", default="bf16")
    ap.add_argument("--gemm-backend", default=None,
                    help="GEMM backend registry name (e.g. jnp_spoga, "
                         "pallas_spoga_dequant, pallas_interpret); "
                         "default: platform auto-selection")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    cfg = cfg.with_(quant_mode=args.quant_mode, gemm_backend=args.gemm_backend)
    tcfg = TrainConfig(learning_rate=args.lr, warmup_steps=5, total_steps=args.steps)
    _, losses = train_loop(cfg, tcfg, steps=args.steps, batch=args.batch,
                           seq=args.seq, ckpt_dir=args.ckpt_dir)
    print(f"[train] first loss {losses[0]:.4f} -> last loss {losses[-1]:.4f}")


if __name__ == "__main__":
    main()

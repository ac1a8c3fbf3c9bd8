"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch <id> [--smoke] \
        --steps 200 [--ckpt-dir /tmp/ckpt] [--resume]

Runs the real loop: data pipeline -> jitted train step (sharded when >1
device) -> checkpoint manager (async, versioned; Young's interval decides
cadence) -> restart.  On this CPU container the smoke configs train a real
~small model; on a pod the full configs ride the same code path through the
bundles in launch/steps.py.
"""
from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager, young_interval
from repro.configs.registry import get_arch
from repro.data.pipeline import lm_batches, dlrm_batches, gnn_batch
from repro.dist.sharding import TRAIN_RULES
from repro.launch.compile_cache import use_compile_cache
from repro.optim.adamw import (adamw_init, adamw_update, clip_by_global_norm,
                               cosine_schedule)


def train_lm(cfg, steps: int, ckpt_dir, resume: bool, batch: int = 8,
             seq: int = 64, log_every: int = 10, lr: float = 1e-3,
             weight_decay: float = 0.01):
    from repro.models import transformer as tf
    params = tf.init_params(cfg, jax.random.key(0))
    opt = adamw_init(params)
    lr_fn = cosine_schedule(lr, warmup_steps=max(steps // 10, 1),
                            total_steps=steps)

    @jax.jit
    def step_fn(params, opt, batch):
        (loss, m), grads = jax.value_and_grad(
            lambda p: tf.loss_fn(cfg, p, batch, TRAIN_RULES),
            has_aux=True)(params)
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        params, opt = adamw_update(params, grads, opt, lr=lr_fn(opt.step),
                                   weight_decay=weight_decay)
        return params, opt, loss, gnorm

    mgr = CheckpointManager(ckpt_dir, async_writes=True) if ckpt_dir else None
    start = 0
    if mgr and resume and mgr.latest_step() is not None:
        start, (params, opt) = mgr.restore(None, (params, opt))
        print(f"resumed from step {start}")
    # paper Eq. 3: checkpoint interval given MTBF; for short jobs the
    # interval exceeds the job and we only checkpoint at the end
    interval_steps = max(1, int(young_interval(2.0, 365 * 24 * 3600, 64)))

    losses = []
    t0 = time.time()
    for i, batch_data in enumerate(
            lm_batches(cfg.vocab_size, batch, seq, seed=start), start=start):
        if i >= steps:
            break
        params, opt, loss, gnorm = step_fn(params, opt, batch_data)
        losses.append(float(loss))
        if i % log_every == 0:
            tput = (i - start + 1) * batch * seq / (time.time() - t0)
            print(f"step {i} loss {float(loss):.4f} gnorm {float(gnorm):.3f} "
                  f"tok/s {tput:.0f}", flush=True)
        if mgr and (i + 1) % min(interval_steps, 100) == 0:
            mgr.save(i + 1, (params, opt))
    if mgr:
        mgr.save(steps, (params, opt), blocking=True)
        mgr.wait()
    return params, losses


def train_gnn(cfg, steps: int, log_every: int = 10):
    from repro.launch.steps import GNN_MODULES
    from repro.models.gnn.api import gnn_loss
    mod = GNN_MODULES[cfg.kind]
    batch = gnn_batch(cfg, seed=0)
    params = mod.init_params(cfg, jax.random.key(0))
    opt = adamw_init(params)

    @jax.jit
    def step_fn(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: gnn_loss(cfg, mod.forward(cfg, p, batch), batch))(
            params)
        grads, _ = clip_by_global_norm(grads, 1.0)
        params, opt = adamw_update(params, grads, opt, lr=1e-3)
        return params, opt, loss

    losses = []
    for i in range(steps):
        params, opt, loss = step_fn(params, opt, batch)
        losses.append(float(loss))
        if i % log_every == 0:
            print(f"step {i} loss {float(loss):.4f}", flush=True)
    return params, losses


def train_dlrm(cfg, steps: int, batch: int = 256, log_every: int = 10):
    from repro.models import dlrm as dl
    params = dl.init_params(cfg, jax.random.key(0))
    opt = adamw_init(params)

    @jax.jit
    def step_fn(params, opt, batch):
        (loss, m), grads = jax.value_and_grad(
            lambda p: dl.loss_fn(cfg, p, batch, TRAIN_RULES),
            has_aux=True)(params)
        grads, _ = clip_by_global_norm(grads, 10.0)
        params, opt = adamw_update(params, grads, opt, lr=1e-3)
        return params, opt, loss

    losses = []
    for i, b in enumerate(dlrm_batches(cfg, batch)):
        if i >= steps:
            break
        params, opt, loss = step_fn(params, opt, b)
        losses.append(float(loss))
        if i % log_every == 0:
            print(f"step {i} loss {float(loss):.4f}", flush=True)
    return params, losses


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    spec = get_arch(args.arch)
    cfg = spec.smoke_config() if args.smoke else spec.full_config()
    if spec.kind in ("lm", "moe"):
        _, losses = train_lm(cfg, args.steps, args.ckpt_dir, args.resume)
    elif spec.kind == "gnn":
        _, losses = train_gnn(cfg, args.steps)
    else:
        _, losses = train_dlrm(cfg, args.steps)
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")


if __name__ == "__main__":
    main()

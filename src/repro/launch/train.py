"""Training driver: data pipeline + sharded train step + checkpoint/restart.

Runs real steps on the host mesh (CPU container: 1 device; production: the
same code under make_production_mesh on TPU).  Wires every fault-tolerance
piece: atomic checkpoints, restore-on-start, heartbeats, restart policy.

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b \
        --reduced --steps 50 --ckpt /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import DataConfig, global_batch_for_step
from repro.dist.checkpoint import latest_step, restore_checkpoint, \
    save_checkpoint
from repro.dist.fault import Heartbeat, StragglerMonitor
from repro.dist.sharding import use_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import init_params, split_tree
from repro.train import AdamWConfig, TrainState, adamw_init, make_train_step


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--save-every", type=int, default=20)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh()
    opt_cfg = AdamWConfig(lr=args.lr, schedule=cfg.lr_schedule,
                          total_steps=args.steps,
                          warmup_steps=max(args.steps // 20, 1))
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch)

    with use_mesh(mesh):
        params, _ = split_tree(init_params(cfg, jax.random.PRNGKey(0)))
        state = TrainState(params=params, opt=adamw_init(params), err=None)
        start = 0
        if args.ckpt:
            last = latest_step(args.ckpt)
            if last is not None:
                state, _ = restore_checkpoint(args.ckpt, state, step=last)
                start = last
                print(f"restored step {start}")
        step_fn = jax.jit(make_train_step(cfg, opt_cfg,
                                          n_micro=args.n_micro))
        hb = Heartbeat(args.ckpt or "/tmp/hb", f"host{jax.process_index()}")
        mon = StragglerMonitor()
        for step in range(start, args.steps):
            t0 = time.time()
            batch = jax.tree.map(jnp.asarray,
                                 global_batch_for_step(dcfg, step))
            state, metrics = step_fn(state, batch)
            dt = time.time() - t0
            mon.observe(f"host{jax.process_index()}", dt)
            hb.beat(step)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"{dt*1e3:.0f}ms", flush=True)
            if args.ckpt and ((step + 1) % args.save_every == 0
                              or step == args.steps - 1):
                save_checkpoint(args.ckpt, step + 1, state)
        return float(metrics["loss"])


if __name__ == "__main__":
    main()

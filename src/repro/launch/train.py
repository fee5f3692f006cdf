"""Production training launcher.

On real TPU hardware this runs the pjit train step on the production mesh
for any assigned architecture:

  python -m repro.launch.train --arch qwen3-1.7b --steps 100 [--multipod]

On CPU (this container) use ``--local`` to train reduced/tiny configs —
the same code path minus the mesh (examples/train_reasoner.py wraps it for
the synthetic reasoning model).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.data.pipeline import device_put_batch, train_batches
from repro.data.synthetic import ChainTask
from repro.launch.mesh import local_ctx, make_ctx
from repro.models import Model
from repro.training.checkpoint import save_checkpoint
from repro.training.optimizer import AdamWConfig
from repro.training.train_loop import (
    TrainConfig,
    init_train_state,
    jit_train_step,
)
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--local", action="store_true",
                    help="single-device (CPU) run on the reduced config")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced or args.local:
        cfg = cfg.reduced()
    ctx = local_ctx() if args.local else make_ctx(multi_pod=args.multipod)
    model = Model(cfg, ctx, attn_impl="xla")

    task = ChainTask(seq_len=args.seq or 96)
    state = init_train_state(model, jax.random.PRNGKey(0))
    tcfg = TrainConfig(opt=AdamWConfig(lr=args.lr, total_steps=args.steps),
                       remat=not args.local)
    it = train_batches(task, args.batch, seed=0)
    batch0 = device_put_batch(model, next(it))
    step_fn = jit_train_step(model, tcfg, state, batch0)

    t0 = time.time()
    for i, batch in zip(range(args.steps), it):
        batch = device_put_batch(model, batch)
        state, metrics = step_fn(state, batch)
        if i % 20 == 0:
            print(f"step {i}: loss={float(metrics['loss']):.4f} "
                  f"acc={float(metrics['accuracy']):.3f} "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)", flush=True)
    if args.ckpt:
        save_checkpoint(args.ckpt, state.params)
        print(f"saved {args.ckpt}")


if __name__ == "__main__":
    main()

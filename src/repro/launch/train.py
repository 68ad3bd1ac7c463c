"""Production training launcher.

On a real TPU cluster this process runs per host (jax.distributed handles
rendezvous); on this container it drives the same code path over the local
device. The mesh comes from --mesh {host|single|multi}; "single"/"multi"
are the production meshes (dry-run scale) and require the forced-device-
count env (use launch/dryrun.py for compile-only checks there).

Example (CPU-runnable):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --reduce --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.ckpt import restore, save
from repro.configs import SHAPES, get_config, reduced
from repro.data import DataConfig, DataPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import get_model
from repro.optim import AdamWConfig
from repro.runtime import TrainOpts, init_train_state, make_train_step
from repro.runtime.sharding import (batch_specs, named, param_specs,
                                    zero1_specs)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduce", action="store_true",
                    help="width-reduced config for CPU runs")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--data", type=int, default=1, help="mesh data axis")
    ap.add_argument("--model", type=int, default=1, help="mesh model axis")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduced(cfg)
    cfg = cfg.replace(dtype="float32")
    model = get_model(cfg)
    mesh = make_host_mesh(args.data, args.model)
    print(f"training {cfg.name} ({cfg.param_count()/1e6:.1f}M params) on "
          f"mesh {dict(mesh.shape)}")

    opts = TrainOpts(opt=AdamWConfig(lr=args.lr, warmup_steps=10,
                                     total_steps=args.steps),
                     microbatches=args.microbatches, remat=args.remat,
                     loss_chunk=min(64, args.seq))
    state = init_train_state(model, jax.random.PRNGKey(0), opts)
    start = 0
    if args.ckpt_dir:
        try:
            state, start = restore(args.ckpt_dir, jax.eval_shape(lambda: state))
            print(f"resumed from step {start}")
        except FileNotFoundError:
            pass

    state_shape = jax.eval_shape(lambda: state)
    pspecs = param_specs(cfg, state_shape["params"], mesh)
    step = jax.jit(make_train_step(model, opts))

    data = DataPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                                   batch_size=args.batch))
    t0 = time.time()
    with mesh:
        for i in range(start, args.steps):
            state, metrics = step(state, data.batch_at(i))
            if (i + 1) % 10 == 0:
                print(f"step {i+1:4d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.2f}")
            if args.ckpt_dir and (i + 1) % 25 == 0:
                save(state, args.ckpt_dir, step=i + 1, keep=2)
    dt = time.time() - t0
    toks = args.batch * args.seq * (args.steps - start)
    print(f"done: {toks/dt:,.0f} tok/s")


if __name__ == "__main__":
    main()

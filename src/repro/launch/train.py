"""Distributed training launcher.

On real hardware this runs under ``jax.distributed`` with the production
mesh; on this CPU container it runs the same code over the host mesh with a
reduced config (the dry-run covers the full-scale lowering).

  PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \
      --steps 100 --reduced [--model-parallel 2]
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.launch import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import Model
from repro.training import checkpoint, optimizer
from repro.training.data import DataConfig, SyntheticCorpus
from repro.training.train_loop import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="h2o-danube-1.8b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU container)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 pod mesh (requires 256 devices)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.reduced else get_config(args.arch)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh(args.model_parallel))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    model = Model(cfg, remat=not args.reduced)
    policy = shd.MeshPolicy(mesh, cfg)
    ocfg = optimizer.OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 5),
                               total_steps=args.steps)
    with jax.sharding.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        p_shape = jax.eval_shape(lambda: params)
        p_shard = shd.param_shardings(p_shape, mesh, cfg)
        params = jax.device_put(params, p_shard)
        opt_state = optimizer.init(params)
        step_fn = jax.jit(make_train_step(model, ocfg, policy),
                          donate_argnums=(0, 1))

        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                          global_batch=args.batch)
        corpus = SyntheticCorpus(dcfg)
        t0 = time.monotonic()
        for step, batch in enumerate(corpus.batches()):
            if step >= args.steps:
                break
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt_state, m = step_fn(params, opt_state, batch)
            if step % 10 == 0 or step == args.steps - 1:
                dt = time.monotonic() - t0
                print(f"step {step:5d} loss {float(m['loss']):.4f} "
                      f"gnorm {float(m['grad_norm']):.3f} "
                      f"({dt:.1f}s)")
        if args.ckpt_dir:
            path = checkpoint.save(args.ckpt_dir, args.steps,
                                   {"params": params})
            print("checkpoint:", path)


if __name__ == "__main__":
    main()

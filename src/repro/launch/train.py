"""Training driver: data pipeline -> sharded train_step -> checkpoints.

CPU-scale by default (reduced configs, host mesh); the same driver lowers
onto the production mesh on real hardware.  Fault-tolerance wiring:
`--simulate-failure N` raises at step N to exercise restart-from-checkpoint.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.registry import SHAPES, ShapeSpec, get_arch
from repro.data import DataConfig, synthetic_batch
from repro.distributed import sharding as shd
from repro.distributed.fault_tolerance import StepTimer
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import abstract_params, build_cell, family_fns
from repro.optim import OptConfig, adamw_init


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--simulate-failure", type=int, default=-1)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)

    arch = get_arch(args.arch, smoke=args.smoke)
    cfg = arch.model
    shape = ShapeSpec("cli", "train", args.seq, args.batch)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh(args.data_parallel, args.model_parallel))
    opt_cfg = OptConfig(lr=args.lr, total_steps=max(args.steps, 10),
                        warmup_steps=max(2, args.steps // 20))

    cell = build_cell(arch, shape, mesh, opt_cfg=opt_cfg)
    fns = family_fns(arch)

    with mesh:
        params = jax.jit(fns["init"],
                         out_shardings=cell.in_shardings[0])(
            jax.random.PRNGKey(0))
        opt_state = jax.jit(adamw_init,
                            out_shardings=cell.in_shardings[1])(params)
        step_fn = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                          out_shardings=cell.out_shardings,
                          donate_argnums=cell.donate_argnums)

        dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch)
        start = 0
        ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        if ckpt and args.resume and ckpt.latest_step() is not None:
            start, (params, opt_state) = ckpt.restore((params, opt_state))
            print(f"resumed from step {start}")

        timer = StepTimer()
        for step in range(start, args.steps):
            host = synthetic_batch(dcfg, step)
            batch = {"tokens": host["tokens"], "labels": host["labels"]}
            if arch.family == "vlm":
                batch["image_embeds"] = np.zeros(
                    (args.batch, arch.n_img_tokens, cfg.d_model), np.float32)
            if arch.family == "encdec":
                batch = {
                    "audio_embeds": np.random.default_rng(step).standard_normal(
                        (args.batch, arch.t_enc, cfg.d_model)).astype(np.float32),
                    "tokens": host["tokens"][:, : arch.dec_len],
                    "labels": host["labels"][:, : arch.dec_len],
                }
            if step == args.simulate_failure:
                raise RuntimeError("simulated node failure")
            with timer:
                params, opt_state, metrics = step_fn(params, opt_state, batch)
                loss = float(metrics["loss"])
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.2f} "
                      f"dt {timer.last:.3f}s"
                      + (" [straggling]" if timer.is_straggling else ""),
                      flush=True)
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, (params, opt_state))
        if ckpt:
            ckpt.save(args.steps, (params, opt_state))
            ckpt.wait()
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())

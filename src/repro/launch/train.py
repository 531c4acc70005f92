"""End-to-end training driver.

    python -m repro.launch.train --arch tinyllama-1.1b --steps 300 \
        --smoke --ckpt-dir /tmp/ckpt [--resume]

``--smoke`` runs the reduced config of the same family on the host devices
(what the container can execute); the full config + production mesh path is
the same code with ``--smoke`` omitted (requires the real pod).  Features
exercised either way: sharded params/optimizer, deterministic data pipeline,
heartbeats, periodic async checkpoints, crash-resume.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, smoke_config
from repro.data.lm_data import DataConfig, batch_at_step
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.train import checkpoint as ckpt
from repro.train.fault import Heartbeat
from repro.train.loop import make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on host devices")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_arch(args.arch)
    mesh = make_host_mesh()
    print(f"[train] arch={cfg.name} mesh={dict(mesh.shape)} "
          f"B={args.batch} S={args.seq}")

    with jax.set_mesh(mesh):
        step_fn, p_specs, o_specs, init_opt = make_train_step(
            cfg, mesh, lr=args.lr, total_steps=args.steps, donate=False)
        params = T.init_params(cfg, jax.random.key(args.seed), jnp.float32)
        opt_state = init_opt(params)

        start_step = 0
        if args.resume and args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
            (params, opt_state), start_step = ckpt.restore(
                (params, opt_state), args.ckpt_dir)
            print(f"[train] resumed from step {start_step}")

        dcfg = DataConfig(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
        hb = Heartbeat(args.ckpt_dir, f"host{jax.process_index()}") \
            if args.ckpt_dir else None
        writer = None
        t0 = time.time()
        for step in range(start_step, args.steps):
            batch = batch_at_step(dcfg, step)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            if cfg.frontend:
                emb = jax.random.normal(
                    jax.random.key(step + 1), (args.batch, args.seq, cfg.d_model),
                    jnp.float32) * 0.02
                batch = {"embeds": emb,
                         "labels": batch["labels"] % cfg.vocab_size}
            else:
                batch = {"tokens": batch["tokens"] % cfg.vocab_size,
                         "labels": batch["labels"] % cfg.vocab_size}
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if hb:
                hb.beat(step)
            if step % args.log_every == 0 or step == args.steps - 1:
                dt = time.time() - t0
                print(f"[train] step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"({dt:.1f}s)", flush=True)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                if writer is not None:
                    writer.join()
                writer = ckpt.save(step + 1, (params, opt_state),
                                   args.ckpt_dir, async_write=True)
        if writer is not None:
            writer.join()
        print(f"[train] done: final loss {float(metrics['loss']):.4f}")


if __name__ == "__main__":
    main()

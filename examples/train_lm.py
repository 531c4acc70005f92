"""End-to-end LM training driver (deliverable b: ~100M model, few hundred
steps) with checkpoints + crash-resume.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

Uses a ~100M-param reduced tinyllama-family config on the host devices; the
identical code path scales to the production mesh via launch/train.py.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.data.lm_data import DataConfig, batch_at_step
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.train import checkpoint as ckpt
from repro.train.loop import make_train_step

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=300)
ap.add_argument("--batch", type=int, default=8)
ap.add_argument("--seq", type=int, default=256)
ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
args = ap.parse_args()

# ~100M params: tinyllama family, narrowed
cfg = dataclasses.replace(
    get_arch("tinyllama-1.1b"), name="tinyllama-100m",
    n_layers=8, d_model=768, n_heads=12, n_kv_heads=4, d_ff=2048,
    vocab_size=8192)
from repro.configs.base import param_count
print(f"model: {cfg.name} ({param_count(cfg)[0] / 1e6:.0f}M params)")

mesh = make_host_mesh()
with jax.set_mesh(mesh):
    step_fn, *_, init_opt = make_train_step(cfg, mesh, lr=3e-4,
                                            total_steps=args.steps,
                                            donate=False)
    params = T.init_params(cfg, jax.random.key(0), jnp.float32)
    opt_state = init_opt(params)
    start = 0
    if ckpt.latest_step(args.ckpt_dir) is not None:
        (params, opt_state), start = ckpt.restore((params, opt_state),
                                                  args.ckpt_dir)
        print(f"resumed from step {start}")

    dcfg = DataConfig(cfg.vocab_size, args.seq, args.batch, seed=0)
    t0 = time.time()
    for step in range(start, args.steps):
        b = batch_at_step(dcfg, step)
        params, opt_state, m = step_fn(
            params, opt_state,
            {"tokens": jnp.asarray(b["tokens"]),
             "labels": jnp.asarray(b["labels"])})
        if step % 20 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(m['loss']):.4f}  "
                  f"({time.time() - t0:.0f}s)", flush=True)
        if (step + 1) % 100 == 0:
            ckpt.save(step + 1, (params, opt_state), args.ckpt_dir)
    print("training done; checkpoint in", args.ckpt_dir)

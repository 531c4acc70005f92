"""Distributed train-step factory (pjit) + per-shape input specs.

``make_train_step(cfg, mesh)`` builds the jitted step with full sharding
annotations: params/optimizer sharded per launch.sharding rules, batch over
the dp axes, gradients clipped + AdamW, optional int8 error-feedback
compression modeling the cross-pod wire format.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.launch.mesh import dp_axes
from repro.launch.sharding import batch_spec, param_shardings
from repro.models import transformer as T
from repro.optim import adamw, clip_by_global_norm, linear_warmup_cosine
from repro.optim.compression import ef_compress_grads


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}


def input_specs(cfg: ArchConfig, shape: str, dtype=jnp.bfloat16) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    sp = SHAPES[shape]
    B, S = sp.global_batch, sp.seq_len
    f32 = jnp.float32
    i32 = jnp.int32
    if sp.kind == "train":
        if cfg.frontend:
            return {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), dtype),
                    "labels": jax.ShapeDtypeStruct((B, S), i32)}
        return {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32)}
    if sp.kind == "prefill":
        if cfg.frontend:
            return {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), dtype)}
        return {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    # decode: one new token against a seq_len cache
    if cfg.frontend:
        return {"embeds": jax.ShapeDtypeStruct((B, 1, cfg.d_model), dtype),
                "length": jax.ShapeDtypeStruct((), i32)}
    return {"token": jax.ShapeDtypeStruct((B,), i32),
            "length": jax.ShapeDtypeStruct((), i32)}


def shape_supported(cfg: ArchConfig, shape: str) -> tuple[bool, str]:
    """long_500k only for sub-quadratic archs (DESIGN.md §5)."""
    if shape == "long_500k" and not cfg.long_context:
        return False, ("full-attention arch: 512k-token decode cell skipped "
                       "by design (see DESIGN.md §5)")
    return True, ""


def make_train_step(cfg: ArchConfig, mesh, *, lr: float = 3e-4,
                    warmup: int = 100, total_steps: int = 10000,
                    clip_norm: float = 1.0, compress_pod_grads: bool = False,
                    param_dtype=jnp.bfloat16, donate: bool = True):
    """Returns (train_step, params_shardings, opt_shardings, batch_shardings).

    train_step(params, opt_state, batch) -> (params, opt_state, metrics)
    """
    from repro.optim.optim import OptState

    init_opt, update_opt = adamw(
        lr=linear_warmup_cosine(lr, warmup, total_steps),
        b1=0.9, b2=0.95, weight_decay=0.1)

    params_shape = jax.eval_shape(
        lambda k: T.init_params(cfg, k, param_dtype), jax.random.key(0))
    p_specs = param_shardings(cfg, mesh, params_shape)
    o_specs = OptState(step=P(), mu=p_specs, nu=p_specs)

    def loss_fn(params, batch):
        return T.lm_loss(params, cfg, tokens=batch.get("tokens"),
                         labels=batch["labels"], embeds=batch.get("embeds"),
                         remat=True)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        if compress_pod_grads:
            # int8 + error feedback models the cross-pod wire format; the
            # EF residual is recomputed per-step (stateless approximation
            # of the EF buffer: residual feeds the *same* step's update)
            grads, _ = ef_compress_grads(grads, None)
        params, opt_state = update_opt(grads, opt_state, params)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "step": opt_state.step}
        return params, opt_state, metrics

    jitted = jax.jit(
        train_step,
        # batch spec inferred on call
        in_shardings=(p_specs, o_specs, None),
        out_shardings=(p_specs, o_specs, None),
        donate_argnums=(0, 1) if donate else (),
    )
    return jitted, p_specs, o_specs, init_opt


def make_train_step_lowerable(cfg: ArchConfig, mesh, shape: str,
                              accum_steps: int = 1, **kw):
    """Fully-specified jitted step + abstract inputs, ready to .lower().

    ``accum_steps > 1`` = gradient accumulation: the global batch is split
    into k microbatches scanned sequentially; activation working set (the
    dominant temp-memory term for the >300B archs) shrinks ~k x at the
    cost of k x more weight re-reads (FSDP gathers per microbatch).
    """
    sp = SHAPES[shape]
    assert sp.kind == "train", shape
    assert sp.global_batch % accum_steps == 0, (shape, accum_steps)
    init_opt, update_opt = adamw(
        lr=linear_warmup_cosine(kw.get("lr", 3e-4), 100, 10000),
        b1=0.9, b2=0.95, weight_decay=0.1)

    params_shape = jax.eval_shape(
        lambda k: T.init_params(cfg, k, kw.get("param_dtype", jnp.bfloat16)),
        jax.random.key(0))
    from repro.optim.optim import OptState
    p_specs = param_shardings(cfg, mesh, params_shape)
    o_specs = OptState(step=P(), mu=p_specs, nu=p_specs)
    opt_shape = jax.eval_shape(init_opt, params_shape)

    batch_shape = input_specs(cfg, shape)
    b_specs = {k: P(dp_axes(mesh), *([None] * (len(v.shape) - 1)))
               for k, v in batch_shape.items()}

    def loss_fn(params, batch):
        return T.lm_loss(params, cfg, tokens=batch.get("tokens"),
                         labels=batch["labels"], embeds=batch.get("embeds"),
                         remat=True)

    def grads_of(params, batch):
        if accum_steps == 1:
            return jax.value_and_grad(loss_fn)(params, batch)

        def micro(carry, mb):
            loss_acc, grad_acc = carry
            # microbatch keeps its batch-over-dp sharding
            mb = {k: T.constrain_batch(v) for k, v in mb.items()}
            l, g = jax.value_and_grad(loss_fn)(params, mb)
            return (loss_acc + l,
                    jax.tree.map(lambda a, b: a + b, grad_acc, g)), None

        micro_batches = {
            k: v.reshape(accum_steps, v.shape[0] // accum_steps, *v.shape[1:])
            for k, v in batch.items()}
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, grads), _ = jax.lax.scan(
            micro, (jnp.zeros(()), zero), micro_batches)
        inv = 1.0 / accum_steps
        return loss * inv, jax.tree.map(lambda g: g * inv, grads)

    def train_step(params, opt_state, batch):
        loss, grads = grads_of(params, batch)
        grads, gnorm = clip_by_global_norm(grads, kw.get("clip_norm", 1.0))
        if kw.get("compress_pod_grads", False):
            grads, _ = ef_compress_grads(grads, None)
        params, opt_state = update_opt(grads, opt_state, params)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    jitted = jax.jit(
        train_step,
        in_shardings=(p_specs, o_specs, b_specs),
        out_shardings=(p_specs, o_specs, None),
    )
    return jitted, (params_shape, opt_shape, batch_shape)

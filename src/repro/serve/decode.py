"""Serving steps: prefill + decode (+ FoG early-exit decode), pjit-ready."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.policy import FogPolicy, margin_backend
from repro.launch.mesh import dp_axes
from repro.launch.sharding import cache_shardings, param_shardings
from repro.models import transformer as T
from repro.models.fog_exit import decode_step_fog
from repro.train.loop import SHAPES, input_specs


def make_serve_step(cfg: ArchConfig, mesh, shape: str, *, fog: bool = False,
                    policy: FogPolicy | None = None,
                    fog_thresh: float = 0.5, fog_backend: str = "reference",
                    param_dtype=jnp.bfloat16):
    """Jitted one-token decode with in/out shardings.

    Returns (jitted_fn, (params_shape, cache_shape, inputs_shape)).
    fn(params, cache, token|embeds, length) -> (logits, new_cache[, hops])

    With ``fog=True`` the decode step takes the per-lane runtime knobs as
    *traced* inputs — fn(params, cache, token|embeds, length, thresh [B],
    budget [B]) — so a single compiled program serves mixed-QoS traffic;
    ``inputs_shape`` gains matching ``fog_thresh`` / ``fog_budget``
    entries.  ``policy`` supplies the static knobs (confidence backend);
    the legacy ``fog_thresh`` / ``fog_backend`` kwargs are folded into a
    policy when none is given.
    """
    sp = SHAPES[shape]
    assert sp.kind == "decode", shape
    B, S = sp.global_batch, sp.seq_len
    if policy is None:
        policy = FogPolicy(threshold=fog_thresh, backend=fog_backend)
    gate_backend = margin_backend(policy.backend)

    params_shape = jax.eval_shape(
        lambda k: T.init_params(cfg, k, param_dtype), jax.random.key(0))
    p_specs = param_shardings(cfg, mesh, params_shape)
    cache_shape = jax.eval_shape(
        partial(T.cache_init, cfg, B, S, param_dtype))
    c_specs = cache_shardings(cfg, mesh, cache_shape)
    inp = input_specs(cfg, shape)
    dp = dp_axes(mesh)
    import numpy as np
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    bdp = dp if B % dp_size == 0 else ()   # batch=1 (long_500k): replicate
    i_specs = {k: (P(bdp, *([None] * (len(v.shape) - 1))) if v.shape else P())
               for k, v in inp.items()}

    logit_m = "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None
    if fog:
        def step(params, cache, token, length, thresh, budget, embeds=None):
            lanes = policy.replace(threshold=thresh, hop_budget=budget)
            logits, cache, hops = decode_step_fog(
                params, cfg, token, cache, length, lanes,
                embeds=embeds, backend=gate_backend)
            return logits, cache, hops
        out_specs = (P(bdp, logit_m), c_specs, P(bdp))
        inp = dict(inp)
        inp["fog_thresh"] = jax.ShapeDtypeStruct((B,), jnp.float32)
        inp["fog_budget"] = jax.ShapeDtypeStruct((B,), jnp.int32)
        knob_specs = (P(bdp), P(bdp))

        if cfg.frontend:
            def wrapped(params, cache, embeds, length, thresh, budget):
                return step(params, cache, None, length, thresh, budget,
                            embeds=embeds)
            in_specs = (p_specs, c_specs, i_specs["embeds"], P(), *knob_specs)
        else:
            def wrapped(params, cache, token, length, thresh, budget):
                return step(params, cache, token, length, thresh, budget)
            in_specs = (p_specs, c_specs, i_specs["token"], P(), *knob_specs)
    else:
        def step(params, cache, token, length, embeds=None):
            logits, cache = T.decode_step(params, cfg, token, cache, length,
                                          embeds=embeds)
            return logits, cache
        out_specs = (P(bdp, logit_m), c_specs)

        if cfg.frontend:
            def wrapped(params, cache, embeds, length):
                return step(params, cache, None, length, embeds=embeds)
            in_specs = (p_specs, c_specs, i_specs["embeds"], P())
        else:
            def wrapped(params, cache, token, length):
                return step(params, cache, token, length)
            in_specs = (p_specs, c_specs, i_specs["token"], P())

    jitted = jax.jit(
        wrapped,
        in_shardings=in_specs,
        out_shardings=out_specs)
    return jitted, (params_shape, cache_shape, inp)


def make_prefill_step(cfg: ArchConfig, mesh, shape: str, *,
                      param_dtype=jnp.bfloat16):
    """Jitted prefill for the prefill_32k cells."""
    sp = SHAPES[shape]
    assert sp.kind == "prefill", shape
    params_shape = jax.eval_shape(
        lambda k: T.init_params(cfg, k, param_dtype), jax.random.key(0))
    p_specs = param_shardings(cfg, mesh, params_shape)
    inp = input_specs(cfg, shape)
    dp = dp_axes(mesh)
    i_specs = {k: P(dp, *([None] * (len(v.shape) - 1))) for k, v in inp.items()}

    def step(params, **inputs):
        return T.prefill(params, cfg, tokens=inputs.get("tokens"),
                         embeds=inputs.get("embeds"))

    key = "embeds" if cfg.frontend else "tokens"

    def wrapped(params, x):
        return step(params, **{key: x})

    jitted = jax.jit(
        wrapped,
        in_shardings=(p_specs, i_specs[key]),
        out_shardings=None)
    return jitted, (params_shape, inp)

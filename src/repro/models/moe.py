"""Mixture-of-Experts layer (grok-1 8e top-2, deepseek-v3 1+256e top-8,
jamba 16e top-2).

Token-choice top-k routing with per-expert capacity (GShard discipline,
TPU-native): instead of ragged gather/scatter (GPU megablocks style), each
expert selects its top-`capacity` tokens by router score with a vmapped
``lax.top_k`` and computes a dense [E, cap, d] x [E, d, f] grouped einsum —
MXU-shaped, statically bounded, and partitionable with experts on the
`model` mesh axis (EP).  Overflow tokens beyond capacity are dropped (their
residual passes through), underflow slots are masked to zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig


def init_moe(key, cfg: ArchConfig, dtype) -> dict:
    d = cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    E = cfg.n_experts
    ks = jax.random.split(key, 5)
    init = lambda k, *sh: (jax.random.normal(k, sh) / np.sqrt(sh[-2])).astype(dtype)
    p = {
        "router": (jax.random.normal(ks[0], (d, E)) * 0.02).astype(jnp.float32),
        "w_gate": init(ks[1], E, d, f),
        "w_up": init(ks[2], E, d, f),
        "w_down": init(ks[3], E, f, d),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": init(k1, d, fs), "w_up": init(k2, d, fs),
            "w_down": init(k3, fs, d),
        }
    return p


def _capacity(n_tokens: int, cfg: ArchConfig) -> int:
    cap = int(n_tokens * cfg.experts_per_token * cfg.capacity_factor
              / max(cfg.n_experts, 1))
    cap = max(8, (cap + 7) // 8 * 8)    # pad to 8 for TPU lane alignment
    return min(cap, n_tokens)


def _constrain(x: jax.Array, *parts) -> jax.Array:
    """with_sharding_constraint iff an ambient mesh is set (no-op in tests)."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return x
    parts = tuple(pp if (pp is None or
                         x.shape[i] % mesh.shape[pp] == 0) else None
                  for i, pp in enumerate(parts))
    return jax.lax.with_sharding_constraint(x, P(*parts))


def moe_ffn(p: dict, cfg: ArchConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (out [B, S, d], aux_loss scalar).

    Routing is GROUP-LOCAL (GShard groups == batch rows): each sequence
    routes its own S tokens with per-row expert capacity.  This keeps every
    gather/scatter *within a data shard* — global-top-k routing would make
    XLA all-gather the full [T, d] token array onto every device (measured:
    457 GiB/device temp for deepseek-v3 train_4k).  Expert compute is a
    grouped einsum with experts sharded on the `model` axis (EP): the
    dispatch crossing data->expert shards is the all-to-all the roofline
    attributes to MoE.
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    cap = _capacity(S, cfg)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                       # [B, S, E]
    topv, topi = jax.lax.top_k(probs, k)                          # [B, S, k]
    # renormalize the selected gates (deepseek/mixtral convention)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    # token-choice gate matrix: probs masked to each token's top-k
    sel = jnp.zeros((B, S, E), jnp.float32)
    bidx = jnp.arange(B)[:, None, None]
    sidx = jnp.arange(S)[None, :, None]
    sel = sel.at[bidx, sidx, topi].set(topv)                      # [B, S, E]

    # per-(row, expert) capacity selection: top-cap tokens of this row
    escore, eidx = jax.lax.top_k(sel.transpose(0, 2, 1), cap)     # [B, E, cap]
    egate = escore * (escore > 0.0)

    xe = jnp.take_along_axis(x[:, None, :, :],
                             eidx[..., None], axis=2)             # [B, E, cap, d]
    xe = _constrain(xe, "data", "model", None, None)
    g = jnp.einsum("becd,edf->becf", xe, p["w_gate"])
    u = jnp.einsum("becd,edf->becf", xe, p["w_up"])
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("becf,efd->becd", h, p["w_down"])             # [B, E, cap, d]
    ye = ye * egate[..., None].astype(ye.dtype)
    ye = _constrain(ye, "data", "model", None, None)

    out = jnp.zeros((B, S, d), ye.dtype)
    out = out.at[jnp.arange(B)[:, None], eidx.reshape(B, -1)].add(
        ye.reshape(B, E * cap, d), mode="drop")

    if cfg.n_shared_experts:
        sp = p["shared"]
        g = jnp.einsum("bsd,df->bsf", x, sp["w_gate"])
        u = jnp.einsum("bsd,df->bsf", x, sp["w_up"])
        out = out + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, sp["w_down"])

    # load-balance auxiliary loss (Switch):  E * sum_e f_e * P_e
    me = jnp.zeros((B, S, E), jnp.float32).at[
        bidx, sidx, topi].set(1.0).mean((0, 1))                   # fraction routed
    pe = probs.mean((0, 1))                                       # mean router prob
    aux = E * jnp.sum(me * pe)
    return out.astype(x.dtype), aux

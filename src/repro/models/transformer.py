"""Config-driven decoder assembly for all assigned architectures.

The layer sequence of every assigned arch is periodic (jamba: 8-layer
attn:mamba blocks with alternating MoE; deepseek: 3 dense layers then
uniform MoE; the rest: period 1), so parameters are stored as

  prefix : list of per-layer dicts (unscanned — deepseek's 3 dense layers)
  stack  : pytree stacked on a leading [n_repeat] axis, scanned with
           ``lax.scan`` so HLO stays O(period) regardless of depth

Scan keeps compile time and HLO size flat for the 61-88-layer archs; remat
(``jax.checkpoint``) wraps the scan body for training memory.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import mamba2, mla, moe
from repro.models.layers import (
    apply_rope, decode_attention, flash_attention, gated_ffn, rmsnorm,
)


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str   # attn | mla | mamba
    ffn: str     # dense | moe


def constrain_batch(x: jax.Array) -> jax.Array:
    """Pin activations to batch-over-dp sharding at block boundaries.

    Without this, XLA's sharding propagation can resolve the FSDP-weight /
    batch-activation contraction conflict by UNSHARDING the batch and
    sharding activations' feature dim over `model` instead (observed on
    deepseek-v3: full-batch [256,4096,*] f32 buffers -> 460 GiB/device).
    No-op when no mesh is set (unit tests) or batch doesn't divide.
    """
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "data" not in mesh.axis_names:
        return x
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    import numpy as _np
    if x.shape[0] % int(_np.prod([mesh.shape[a] for a in dp])) != 0:
        return x
    return jax.lax.with_sharding_constraint(
        x, P(dp, *([None] * (x.ndim - 1))))


def layer_plan(cfg: ArchConfig) -> tuple[list[LayerSpec], list[LayerSpec], int]:
    """(prefix_specs, period_specs, n_repeat)."""
    def spec(i: int) -> LayerSpec:
        if cfg.ssm and not cfg.is_attn_layer(i):
            mixer = "mamba"
        elif cfg.mla:
            mixer = "mla"
        else:
            mixer = "attn"
        return LayerSpec(mixer, "moe" if cfg.is_moe_layer(i) else "dense")

    n_prefix = cfg.moe_first_k_dense
    prefix = [spec(i) for i in range(n_prefix)]
    rem = cfg.n_layers - n_prefix
    if cfg.ssm and cfg.attn_layer_period:
        R = cfg.attn_layer_period
    elif cfg.moe and cfg.moe_period > 1:
        R = cfg.moe_period
    else:
        R = 1
    assert rem % R == 0, (cfg.name, rem, R)
    period = [spec(n_prefix + j) for j in range(R)]
    return prefix, period, rem // R


# ---------------------------------------------------------------- params ---
def _init_attn(key, cfg: ArchConfig, dtype) -> dict:
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    init = lambda k, *sh: (jax.random.normal(k, sh) / np.sqrt(d)).astype(dtype)
    return {
        "wq": init(ks[0], d, H, hd),
        "wk": init(ks[1], d, K, hd),
        "wv": init(ks[2], d, K, hd),
        "wo": (jax.random.normal(ks[3], (H, hd, d)) / np.sqrt(H * hd)).astype(dtype),
    }


def _init_ffn(key, cfg: ArchConfig, dtype) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    init = lambda k, a, b: (jax.random.normal(k, (a, b)) / np.sqrt(a)).astype(dtype)
    p = {"w_gate": init(k1, d, f), "w_down": init(k3, f, d)}
    if cfg.gated_ffn:
        p["w_up"] = init(k2, d, f)
    return p


def _init_layer(key, cfg: ArchConfig, s: LayerSpec, dtype) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: dict[str, Any] = {"ln1": jnp.zeros((cfg.d_model,), dtype)}
    if s.mixer == "attn":
        p["attn"] = _init_attn(k1, cfg, dtype)
    elif s.mixer == "mla":
        p["attn"] = mla.init_mla(k1, cfg, dtype)
    else:
        p["mamba"] = mamba2.init_mamba(k1, cfg, dtype)
    if s.mixer != "mamba":
        p["ln2"] = jnp.zeros((cfg.d_model,), dtype)
        p["ffn"] = (moe.init_moe(k2, cfg, dtype) if s.ffn == "moe"
                    else _init_ffn(k2, cfg, dtype))
    else:
        # mamba blocks are mixer-only in mamba2; hybrid (jamba) keeps the FFN
        if cfg.attn_layer_period:
            p["ln2"] = jnp.zeros((cfg.d_model,), dtype)
            p["ffn"] = (moe.init_moe(k2, cfg, dtype) if s.ffn == "moe"
                        else _init_ffn(k2, cfg, dtype))
    return p


def init_params(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> dict:
    prefix, period, n_rep = layer_plan(cfg)
    keys = jax.random.split(key, 4 + len(prefix))
    d, V = cfg.d_model, cfg.vocab_size
    params: dict[str, Any] = {
        "embed": (jax.random.normal(keys[0], (V, d)) * 0.02).astype(dtype),
        "out_norm": jnp.zeros((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (jax.random.normal(keys[1], (d, V)) / np.sqrt(d)).astype(dtype)
    params["prefix"] = [
        _init_layer(keys[4 + i], cfg, s, dtype) for i, s in enumerate(prefix)]

    def init_block(k):
        sub = jax.random.split(k, len(period))
        return {f"pos{j}": _init_layer(sub[j], cfg, s, dtype)
                for j, s in enumerate(period)}

    block_keys = jax.random.split(keys[2], n_rep)
    params["stack"] = jax.vmap(init_block)(block_keys)
    if cfg.mtp:
        k1, k2 = jax.random.split(keys[3])
        params["mtp"] = {
            "proj": (jax.random.normal(k1, (2 * d, d)) / np.sqrt(2 * d)).astype(dtype),
            "ln": jnp.zeros((d,), dtype),
        }
    return params


# --------------------------------------------------------------- forward ---
def _attn_train(p, cfg: ArchConfig, h: jax.Array) -> jax.Array:
    B, S, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    q = jnp.einsum("bsd,dhe->bshe", h, p["wq"])
    k = jnp.einsum("bsd,dke->bske", h, p["wk"])
    v = jnp.einsum("bsd,dke->bske", h, p["wv"])
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    out = flash_attention(q, k, v, causal=True)
    return jnp.einsum("bshe,hed->bsd", out, p["wo"])


def _apply_layer_train(p, cfg: ArchConfig, s: LayerSpec, h, aux):
    if s.mixer == "mamba":
        mixed, _ = mamba2.mamba_forward(p["mamba"], cfg, rmsnorm(h, p["ln1"]))
        h = h + mixed
    elif s.mixer == "mla":
        h = h + mla.mla_attention_train(p["attn"], cfg, rmsnorm(h, p["ln1"]))
    else:
        h = h + _attn_train(p["attn"], cfg, rmsnorm(h, p["ln1"]))
    if "ffn" in p:
        x = rmsnorm(h, p["ln2"])
        if s.ffn == "moe":
            y, a = moe.moe_ffn(p["ffn"], cfg, x)
            aux = aux + a
        else:
            y = gated_ffn(x, p["ffn"]["w_gate"], p["ffn"].get("w_up"),
                          p["ffn"]["w_down"], cfg.act)
        h = h + y
    return h, aux


def embed_tokens(params, cfg: ArchConfig, tokens: jax.Array) -> jax.Array:
    h = params["embed"][tokens]
    return h * jnp.asarray(np.sqrt(cfg.d_model), h.dtype)   # gemma-style scale


def unembed(params, cfg: ArchConfig, h: jax.Array) -> jax.Array:
    h = rmsnorm(h, params["out_norm"])
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    return jnp.einsum("...d,dv->...v", h, w).astype(jnp.float32)


def forward(params, cfg: ArchConfig, tokens=None, embeds=None,
            remat: bool = True) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  Returns (hidden [B,S,d], moe_aux scalar)."""
    prefix, period, _ = layer_plan(cfg)
    h = embed_tokens(params, cfg, tokens) if embeds is None else embeds
    aux = jnp.zeros((), jnp.float32)
    for p, s in zip(params["prefix"], prefix):
        h, aux = _apply_layer_train(p, cfg, s, h, aux)

    def block(carry, blk_params):
        h, aux = carry
        h = constrain_batch(h)
        for j, s in enumerate(period):
            h, aux = _apply_layer_train(blk_params[f"pos{j}"], cfg, s, h, aux)
        return (constrain_batch(h), aux), None

    body = jax.checkpoint(block) if remat else block
    (h, aux), _ = jax.lax.scan(body, (h, aux), params["stack"])
    return h, aux


def chunked_ce(params, cfg: ArchConfig, h: jax.Array, labels: jax.Array,
               chunk: int = 512, mask: jax.Array | None = None) -> jax.Array:
    """Cross-entropy without materializing full [B, S, V] f32 logits.

    For 129k-256k vocabularies the f32 logits tensor dominates HBM (gemma-2b
    train_4k: 16.8 GiB/device).  Scanning the unembed+softmax over sequence
    chunks bounds the live logits buffer to [B, chunk, V/model]; the scan
    body is rematerialized so backward recomputes each chunk's logits
    instead of saving them (the "fused CE" every production LM framework
    ships, here in pure JAX)."""
    B, S = labels.shape
    c = min(chunk, S)
    if S % c:
        c = S
    nc = S // c
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    hc = h.reshape(B, nc, c, -1).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nc, c).transpose(1, 0, 2)
    mc = mask.reshape(B, nc, c).transpose(1, 0, 2)

    def body(acc, xs):
        hh, ll, mm = xs
        hh = constrain_batch(hh)
        logits = unembed(params, cfg, hh)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, ll[..., None], axis=-1)[..., 0]
        return acc + (ce * mm).sum(), None

    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, lc, mc))
    return total / jnp.maximum(mask.sum(), 1.0)


def lm_loss(params, cfg: ArchConfig, tokens=None, labels=None, embeds=None,
            aux_coef: float = 0.01, remat: bool = True) -> jax.Array:
    h, aux = forward(params, cfg, tokens=tokens, embeds=embeds, remat=remat)
    loss = chunked_ce(params, cfg, h, labels)
    if cfg.mtp:
        # multi-token prediction: predict t+2 from (h_t, embed(label_t))
        emb_next = embed_tokens(params, cfg, labels)
        mixed = jnp.einsum(
            "bsd,dk->bsk",
            jnp.concatenate([rmsnorm(h, params["mtp"]["ln"]), emb_next], -1),
            params["mtp"]["proj"])
        labels2 = jnp.roll(labels, -1, axis=1)
        mask2 = jnp.ones_like(labels2, jnp.float32).at[:, -1].set(0.0)
        loss = loss + 0.3 * chunked_ce(params, cfg, mixed, labels2, mask=mask2)
    return loss + aux_coef * aux


# ---------------------------------------------------------------- decode ---
def _cache_init_layer(cfg: ArchConfig, s: LayerSpec, batch: int,
                      max_seq: int, dtype) -> dict:
    if s.mixer == "mamba":
        state, tail = mamba2.mamba_state_init(cfg, batch, dtype)
        return {"state": state, "conv": tail}
    if s.mixer == "mla":
        return mla.mla_cache_init(cfg, batch, max_seq, dtype)
    K, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {"k": jnp.zeros((batch, max_seq, K, hd), dtype),
            "v": jnp.zeros((batch, max_seq, K, hd), dtype)}


def cache_init(cfg: ArchConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    prefix, period, n_rep = layer_plan(cfg)
    pre = [_cache_init_layer(cfg, s, batch, max_seq, dtype) for s in prefix]
    one = lambda: {f"pos{j}": _cache_init_layer(cfg, s, batch, max_seq, dtype)
                   for j, s in enumerate(period)}
    stack = jax.tree.map(lambda x: jnp.broadcast_to(x, (n_rep, *x.shape)), one())
    return {"prefix": pre, "stack": stack}


def _attn_decode(p, cfg: ArchConfig, h, cache, length):
    B = h.shape[0]
    pos = jnp.full((B, 1), length, jnp.int32)
    q = jnp.einsum("bsd,dhe->bshe", h, p["wq"])
    k = jnp.einsum("bsd,dke->bske", h, p["wk"])
    v = jnp.einsum("bsd,dke->bske", h, p["wv"])
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype),
                                             length, axis=1)
    vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype),
                                             length, axis=1)
    out = decode_attention(q, kc, vc, length + 1)
    return jnp.einsum("bshe,hed->bsd", out, p["wo"]), {"k": kc, "v": vc}


def _apply_layer_decode(p, cfg: ArchConfig, s: LayerSpec, h, cache, length):
    x = rmsnorm(h, p["ln1"])
    if s.mixer == "mamba":
        mixed, (st, tail) = mamba2.mamba_decode(p["mamba"], cfg, x,
                                                cache["state"], cache["conv"])
        cache = {"state": st, "conv": tail}
    elif s.mixer == "mla":
        mixed, cache = mla.mla_attention_decode(p["attn"], cfg, x, cache, length)
    else:
        mixed, cache = _attn_decode(p["attn"], cfg, x, cache, length)
    h = h + mixed
    if "ffn" in p:
        x = rmsnorm(h, p["ln2"])
        if s.ffn == "moe":
            y, _ = moe.moe_ffn(p["ffn"], cfg, x)
        else:
            y = gated_ffn(x, p["ffn"]["w_gate"], p["ffn"].get("w_up"),
                          p["ffn"]["w_down"], cfg.act)
        h = h + y
    return h, cache


def decode_step(params, cfg: ArchConfig, token, cache, length,
                embeds=None):
    """One decode step.  token [B] int32 (or embeds [B,1,d]); returns
    (logits [B,V], new_cache)."""
    prefix, period, _ = layer_plan(cfg)
    h = (embed_tokens(params, cfg, token[:, None]) if embeds is None else embeds)
    new_prefix = []
    for p, s, c in zip(params["prefix"], prefix, cache["prefix"]):
        h, c = _apply_layer_decode(p, cfg, s, h, c, length)
        new_prefix.append(c)

    def block(h, xs):
        blk_params, blk_cache = xs
        h = constrain_batch(h)
        new_cache = {}
        for j, s in enumerate(period):
            h, new_cache[f"pos{j}"] = _apply_layer_decode(
                blk_params[f"pos{j}"], cfg, s, h, blk_cache[f"pos{j}"], length)
        return constrain_batch(h), new_cache

    h, new_stack = jax.lax.scan(block, h, (params["stack"], cache["stack"]))
    logits = unembed(params, cfg, h[:, 0])
    return logits, {"prefix": new_prefix, "stack": new_stack}


def prefill(params, cfg: ArchConfig, tokens=None, embeds=None,
            max_seq: int | None = None):
    """Prefill: full forward + caches populated for positions [0, S).

    Returns (last-position logits [B,V], cache).  Caches are padded to
    ``max_seq`` (default S) so decode can continue at length=S.  Used by
    the prefill_32k cells.
    """
    prefix, period, n_rep = layer_plan(cfg)
    B, S = (tokens.shape if tokens is not None else embeds.shape[:2])
    h = embed_tokens(params, cfg, tokens) if embeds is None else embeds
    dtype = h.dtype
    pad_s = (max_seq or S) - S

    def padseq(a):
        return jnp.pad(a, ((0, 0), (0, pad_s)) + ((0, 0),) * (a.ndim - 2)) \
            if pad_s else a

    def mix_with_cache(p, s, h):
        x = rmsnorm(h, p["ln1"])
        if s.mixer == "mamba":
            mixed, (st, tail) = mamba2.mamba_forward(p["mamba"], cfg, x)
            cache = {"state": st, "conv": tail}
        elif s.mixer == "mla":
            pos = jnp.broadcast_to(jnp.arange(S), (B, S))
            c_kv, k_rope = mla._compress_kv(p["attn"], cfg, x, pos)
            mixed = mla.mla_attention_train(p["attn"], cfg, x)
            cache = {"c_kv": padseq(c_kv.astype(dtype)),
                     "k_rope": padseq(k_rope.astype(dtype))}
        else:
            pos = jnp.broadcast_to(jnp.arange(S), (B, S))
            q = jnp.einsum("bsd,dhe->bshe", x, p["attn"]["wq"])
            k = jnp.einsum("bsd,dke->bske", x, p["attn"]["wk"])
            v = jnp.einsum("bsd,dke->bske", x, p["attn"]["wv"])
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)
            out = flash_attention(q, k, v, causal=True)
            mixed = jnp.einsum("bshe,hed->bsd", out, p["attn"]["wo"])
            cache = {"k": padseq(k.astype(dtype)), "v": padseq(v.astype(dtype))}
        h = h + mixed
        if "ffn" in p:
            xf = rmsnorm(h, p["ln2"])
            if s.ffn == "moe":
                y, _ = moe.moe_ffn(p["ffn"], cfg, xf)
            else:
                y = gated_ffn(xf, p["ffn"]["w_gate"], p["ffn"].get("w_up"),
                              p["ffn"]["w_down"], cfg.act)
            h = h + y
        return h, cache

    new_prefix = []
    for p, s in zip(params["prefix"], prefix):
        h, c = mix_with_cache(p, s, h)
        new_prefix.append(c)

    def block(h, blk_params):
        h = constrain_batch(h)
        caches = {}
        for j, s in enumerate(period):
            h, caches[f"pos{j}"] = mix_with_cache(blk_params[f"pos{j}"], s, h)
        return constrain_batch(h), caches

    h, stack_caches = jax.lax.scan(block, h, params["stack"])
    logits = unembed(params, cfg, h[:, -1])
    return logits, {"prefix": new_prefix, "stack": stack_caches}

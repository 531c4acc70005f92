"""Pallas TPU kernel: fused causal flash attention (GQA-aware).

The pure-JAX blocked attention in models/layers.py materializes every
[blk_q, blk_k] score/probability tile at an XLA fusion boundary — measured
at ~70 TB HBM traffic per train step for minicpm3-4b (the dominant roofline
term).  This kernel keeps the whole online-softmax pipeline (qk^T, mask,
exp, rescale, pv) in VMEM: HBM traffic collapses to one q/k/v read + one
output write per layer.

Grid: (batch*kv_head*q_group, nq) — one q block per program, kv scanned
inside with ``jax.lax.fori_loop``; the causal upper triangle is skipped at
block granularity (trip count = ceil((iq+1)*blk_q / blk_k)), which also
removes the ~2x masked-FLOP waste the jnp path pays.

Validated against ref.flash_attention_ref with interpret=True (CPU) over
shape/dtype sweeps in tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.tree_traverse import resolve_interpret


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, blk_q: int,
                  blk_k: int, seq_k: int, causal: bool):
    iq = pl.program_id(1)
    q = q_ref[0]                     # [blk_q, D]
    D = q.shape[-1]
    Dv = v_ref.shape[-1]

    nk = seq_k // blk_k
    if causal:
        n_live = jnp.minimum((iq * blk_q + blk_q + blk_k - 1) // blk_k, nk)
    else:
        n_live = nk

    def body(jk, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(jk * blk_k, blk_k), :]
        v = v_ref[0, pl.ds(jk * blk_k, blk_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [blk_q, blk_k]
        if causal:
            qpos = iq * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            kpos = jk * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[:, None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc = acc * corr[:, None] + pv
        return acc, m_new, l

    acc0 = jnp.zeros((blk_q, Dv), jnp.float32)
    m0 = jnp.full((blk_q,), -jnp.inf)
    l0 = jnp.zeros((blk_q,))
    acc, m, l = jax.lax.fori_loop(0, n_live, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           *, causal: bool = True, blk_q: int = 512,
                           blk_k: int = 512, scale: float | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """q [B,Sq,H,D], k/v [B,Sk,K,Dkv] -> [B,Sq,H,Dv].  H % K == 0.
    ``interpret=None`` derives from the runtime (``resolve_interpret``)."""
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    Dv = v.shape[3]
    G = H // K
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Sk)
    assert Sq % blk_q == 0 and Sk % blk_k == 0
    nq = Sq // blk_q

    # flatten (B, K, G) into one "head-lane" axis; kv broadcast over G
    qf = q.reshape(B, Sq, K, G, D).transpose(0, 2, 3, 1, 4) \
        .reshape(B * K * G, Sq, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3).reshape(B * K, Sk, D), G, axis=0)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3).reshape(B * K, Sk, Dv), G, axis=0)

    # VMEM budget: q block + full k/v stripes per lane
    assert blk_q * D * 4 + Sk * (D + Dv) * 2 < 12 * 2**20, \
        "k/v stripe exceeds VMEM; lower blk sizes or shard sequence"

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, blk_q=blk_q,
                          blk_k=blk_k, seq_k=Sk, causal=causal),
        grid=(B * K * G, nq),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, Sk, Dv), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, Dv), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * K * G, Sq, Dv), q.dtype),
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)

    out = out.reshape(B, K, G, Sq, Dv).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, Sq, H, Dv)

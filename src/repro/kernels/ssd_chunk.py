"""Pallas TPU kernel: Mamba-2 SSD intra-chunk block (fused).

The chunked SSD scan (models/mamba2.py) materializes per-chunk [Q, Q]
decay/score tiles (L = exp(segsum), C·Bᵀ) at XLA fusion boundaries — the
SSM analogue of unfused attention scores, and the residual memory term for
the mamba/jamba cells after the flash-attention fix.  This kernel fuses
the whole intra-chunk computation per (batch, chunk) program:

    per head h:   cum   = cumsum(a_h)                      [Q]
                  L     = exp(cum_i - cum_j) . tril        [Q, Q]  (VMEM)
                  S     = C B^T                            [Q, Q]  (VMEM)
                  y_h   = (S * L) @ xbar_h                 [Q, P]
                  st_h  = (B * exp(cum_Q - cum))^T @ xbar_h [N, P]

emitting y_diag [Q, H, P] and chunk-state summaries [H, P, N]; the cheap
O(nc) inter-chunk recurrence and the C·state_prev off-diagonal term stay
in jnp (they carry no [Q,Q] tiles).  Oracle: ref.ssd_chunk_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tree_traverse import resolve_interpret


def _ssd_chunk_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, st_ref, *,
                      n_heads: int):
    x = x_ref[0]          # [Q, H, P]
    a = a_ref[0]          # [H, Q]
    Bm = b_ref[0]         # [Q, N]
    Cm = c_ref[0]         # [Q, N]
    Q = x.shape[0]

    scores = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)  # [Q,Q]
    tril = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)

    for h in range(n_heads):                      # static unroll over local heads
        ah = a[h].astype(jnp.float32)             # [Q]
        cum = jnp.cumsum(ah)
        L = jnp.where(tril, jnp.exp(cum[:, None] - cum[None, :]), 0.0)
        xh = x[:, h, :].astype(jnp.float32)       # [Q, P]
        y = jnp.dot(scores * L, xh,
                    preferred_element_type=jnp.float32)            # [Q, P]
        decay_end = jnp.exp(cum[-1] - cum)                         # [Q]
        st = jnp.dot((Bm.astype(jnp.float32) * decay_end[:, None]).T,
                     xh * jnp.exp(0.0),
                     preferred_element_type=jnp.float32)           # [N, P]
        y_ref[0, :, h, :] = y.astype(y_ref.dtype)
        st_ref[0, h, :, :] = st.T.astype(st_ref.dtype)             # [P, N]


def ssd_chunk_pallas(xbar: jax.Array, a: jax.Array, Bm: jax.Array,
                     Cm: jax.Array, *, interpret: bool | None = None):
    """Fused intra-chunk SSD.

    xbar [B, nc, Q, H, P] (dt-scaled inputs), a [B, nc, H, Q] (log decays),
    Bm/Cm [B, nc, Q, N]  ->  (y_diag [B, nc, Q, H, P], states [B, nc, H, P, N])
    """
    B, nc, Q, H, P = xbar.shape
    N = Bm.shape[-1]
    # VMEM: x chunk + per-head [Q,Q] tiles
    assert Q * Q * 4 * 2 + Q * (H * P + 2 * N) * 4 < 12 * 2**20, \
        "chunk working set exceeds VMEM; lower ssm_chunk"

    xf = xbar.reshape(B * nc, Q, H, P)
    af = a.reshape(B * nc, H, Q)
    bf = Bm.reshape(B * nc, Q, N)
    cf = Cm.reshape(B * nc, Q, N)

    y, st = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, n_heads=H),
        grid=(B * nc,),
        in_specs=[
            pl.BlockSpec((1, Q, H, P), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, H, Q), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, H, P), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, H, P, N), lambda i: (i, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * nc, Q, H, P), xbar.dtype),
            jax.ShapeDtypeStruct((B * nc, H, P, N), xbar.dtype),
        ],
        interpret=resolve_interpret(interpret),
    )(xf, af, bf, cf)
    return (y.reshape(B, nc, Q, H, P), st.reshape(B, nc, H, P, N))

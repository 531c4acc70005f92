"""Pallas TPU kernel: fused MaxDiff confidence (top-2 margin, no sort).

The ASIC's MaxDiff comparator: one pass max, one masked pass for the second
max, absolute difference.  The batch rides the lane axis (``[C, B]``
blocks, whole 128-lane rows) with the class axis on sublanes, so both maxima
are sublane reductions.  The first maximum is masked as the smallest class
index among the maxima — the same tie rule as a first-max cumsum, without a
scan the TPU compiler does not lower.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tree_traverse import (compiler_params, lane_block,
                                         resolve_interpret, round_up)


def maxdiff_lanes(p: jax.Array) -> jax.Array:
    """MaxDiff margin ``[1, w]`` of class-major ``p [C, w]`` fp32."""
    cls = jax.lax.broadcasted_iota(jnp.int32, p.shape, 0).astype(jnp.float32)
    m1 = jnp.max(p, axis=0, keepdims=True)
    first = jnp.min(jnp.where(p == m1, cls, float(p.shape[0])), axis=0,
                    keepdims=True)
    m2 = jnp.max(jnp.where(cls == first, -jnp.inf, p), axis=0, keepdims=True)
    return jnp.abs(m1 - m2)


def _top2_kernel(prob_ref, out_ref):
    out_ref[...] = maxdiff_lanes(prob_ref[...])


def top2_confidence_pallas(prob: jax.Array, *, block_b: int = 256,
                           interpret: bool | None = None) -> jax.Array:
    """[B, C] -> [B] top-2 margin (computed in fp32, returned in
    ``prob.dtype``).

    ``B`` need not divide ``block_b``: the batch is zero-padded to the next
    block boundary and the padded lanes' margins sliced off.
    """
    B, C = prob.shape
    block_b = lane_block(block_b, B)
    Bp = round_up(B, block_b)
    pT = jnp.pad(prob.astype(jnp.float32), ((0, Bp - B), (0, 0))).T
    out = pl.pallas_call(
        _top2_kernel,
        grid=(Bp // block_b,),
        in_specs=[pl.BlockSpec((C, block_b), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block_b), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Bp), jnp.float32),
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(pT)
    return out[0, :B].astype(prob.dtype)

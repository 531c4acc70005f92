"""Pallas TPU kernel: fused Algorithm-2 inner step (lines 7-11).

Fuses the per-hop state update — masked probability accumulate, hop count,
normalization, MaxDiff margin, liveness gate — into one VMEM pass so the
[B, C] probability state is read and written exactly once per hop instead
of materializing four intermediates in HBM.

The confidence gate takes a per-lane threshold vector: a scalar threshold is
broadcast to ``[B]`` before the call, so mixed-QoS batches (every lane with
its own accuracy/energy trade-off, ``FogPolicy.threshold`` as a vector) run
the same kernel at identical cost.

The batch rides the lane axis: probabilities arrive as ``[C, B]`` blocks of
whole 128-lane rows, per-lane state as ``[1, B]`` rows (the TPU compiler
tiles no rank-1 int8 block), and the margin is ``maxdiff_lanes``.

Precision contract: grove tables are packed (fp32/bf16/int8 — see
``forest.pack.ForestPack``) and the per-hop grove walk dequantizes its
*contribution* rows to fp32 before this kernel sees them, so the
accumulate/normalize/MaxDiff state here is always fp32 regardless of the
table dtype ("int8 loads, fp32 compare/accumulate").  The wrapper enforces
that contract rather than silently accumulating in a narrow dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.top2_confidence import maxdiff_lanes
from repro.kernels.tree_traverse import (compiler_params, lane_block,
                                         resolve_interpret, round_up)


def _aggregate_kernel(prob_ref, contrib_ref, live_ref, hops_ref, thresh_ref,
                      prob_out, hops_out, live_out, margin_out):
    prob = prob_ref[...]           # [C, BB] batch on lanes
    contrib = contrib_ref[...]     # [C, BB]
    live = live_ref[...]           # [1, BB] int32 0/1
    hops = hops_ref[...]           # [1, BB]
    thresh = thresh_ref[...]       # [1, BB] per-lane gate

    prob = prob + contrib * live.astype(prob.dtype)
    hops = hops + live
    margin = maxdiff_lanes(prob / jnp.maximum(hops, 1).astype(prob.dtype))

    prob_out[...] = prob
    hops_out[...] = hops
    live_out[...] = ((live > 0) & (margin < thresh)).astype(jnp.int32)
    margin_out[...] = margin


def grove_aggregate_pallas(prob_acc: jax.Array, contrib: jax.Array,
                           live: jax.Array, hops: jax.Array,
                           thresh: jax.Array, *, block_b: int = 256,
                           interpret: bool | None = None):
    """Fused hop update.  live is bool [B]; thresh is a scalar or per-lane
    [B] vector; returns (prob, hops, live, margin).

    ``B`` need not divide ``block_b``: the batch is dead-lane padded up to
    the next block boundary (padded lanes carry live=0, so their garbage
    margins never gate anything; the thresh vector pads along with them)
    and the outputs are sliced back to ``B``.
    """
    if jnp.issubdtype(contrib.dtype, jnp.integer):
        raise ValueError(
            f"grove_aggregate accumulates in floating point; dequantize "
            f"packed contributions before the hop update (got "
            f"{contrib.dtype})")
    B, C = prob_acc.shape
    block_b = lane_block(block_b, B)
    Bp = round_up(B, block_b)
    pad = Bp - B
    dt = prob_acc.dtype
    cols = lambda a: jnp.pad(a, ((0, pad), (0, 0))).T            # [C, Bp]
    row = lambda v, d: jnp.pad(jnp.broadcast_to(jnp.asarray(v, d), (B,)),
                               (0, pad)).reshape(1, Bp)
    mat = pl.BlockSpec((C, block_b), lambda i: (0, i))
    lanes = pl.BlockSpec((1, block_b), lambda i: (0, i))
    prob, hops, live, margin = pl.pallas_call(
        _aggregate_kernel,
        grid=(Bp // block_b,),
        in_specs=[mat, mat, lanes, lanes, lanes],
        out_specs=[mat, lanes, lanes, lanes],
        out_shape=[
            jax.ShapeDtypeStruct((C, Bp), dt),
            jax.ShapeDtypeStruct((1, Bp), jnp.int32),
            jax.ShapeDtypeStruct((1, Bp), jnp.int32),
            jax.ShapeDtypeStruct((1, Bp), dt),
        ],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(cols(prob_acc), cols(contrib.astype(dt)), row(live, jnp.int32),
      row(hops, jnp.int32), row(thresh, dt))
    return (prob[:, :B].T, hops[0, :B], live[0, :B].astype(bool),
            margin[0, :B])

"""Jit'd public wrappers for the Pallas kernels.

On the CPU container the kernels execute with ``interpret=True`` (the kernel
body runs as jnp on CPU — correctness identical, performance irrelevant); on
a real TPU backend they compile to Mosaic.  Callers never pass ``interpret``
themselves.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.tree_traverse import resolve_interpret, tree_traverse_pallas
from repro.kernels.top2_confidence import top2_confidence_pallas
from repro.kernels.grove_aggregate import grove_aggregate_pallas
from repro.kernels.fused_fog import fused_fog_pallas
from repro.kernels import ref


def _interpret() -> bool:
    return resolve_interpret(None)


@partial(jax.jit, static_argnames=("block_b",))
def tree_traverse(feature, threshold, leaf, x, thr_scale=None,
                  leaf_scale=None, *, block_b: int = 128):
    """Grove bundle eval [B,F] -> [B,C] over packed fp32/bf16/int8 tables
    (Pallas; oracle: ref.tree_traverse_ref).  Leaves stay at the packed
    width in VMEM; int8 values dequantize in-kernel via the per-tree
    scales."""
    return tree_traverse_pallas(feature, threshold, leaf, x,
                                thr_scale, leaf_scale,
                                block_b=block_b, interpret=_interpret())


@partial(jax.jit, static_argnames=("block_b",))
def top2_confidence(prob, *, block_b: int = 256):
    """MaxDiff margin [B,C] -> [B] (Pallas; oracle: ref.top2_confidence_ref)."""
    return top2_confidence_pallas(prob, block_b=block_b, interpret=_interpret())


@partial(jax.jit, static_argnames=("block_b",))
def grove_aggregate(prob_acc, contrib, live, hops, thresh, *, block_b: int = 256):
    """Fused Algorithm-2 hop update; thresh is a scalar or per-lane [B]
    vector (Pallas; oracle: ref.grove_aggregate_ref)."""
    return grove_aggregate_pallas(prob_acc, contrib, live, hops, thresh,
                                  block_b=block_b, interpret=_interpret())


@partial(jax.jit, static_argnames=("max_hops", "block_b", "compact",
                                   "interpret"))
def fused_fog(feature, threshold, leaf, x, start, thresh, budget,
              thr_scale=None, leaf_scale=None, *,
              max_hops: int, block_b: int = 128, compact: bool = True,
              interpret: bool | None = None):
    """Whole Algorithm-2 loop in ONE kernel launch: head-stacked packed
    grove tables [O,G,t,...] pinned in VMEM (leaves at the packed width,
    int8 dequantized in-kernel), per-lane thresh/budget, early-exit
    while_loop inside the kernel.  ``compact`` moves live lanes to a
    prefix each hop and walks only the covering power-of-two prefix
    (bit-identical results); ``interpret=None`` derives from the runtime backend.  Returns
    (proba [B,O,C], hops [B]); oracle: the FogEngine reference backend
    over the same pack."""
    return fused_fog_pallas(feature, threshold, leaf, x, start, thresh,
                            budget, thr_scale, leaf_scale,
                            max_hops=max_hops, block_b=block_b,
                            compact=compact, interpret=interpret)


__all__ = ["tree_traverse", "top2_confidence", "grove_aggregate",
           "fused_fog", "ref"]

"""Distributed FoG — the paper's grove ring mapped onto a TPU mesh.

The ASIC pins grove g to a physical PE and forwards uncertain inputs over a
req/ack handshake to PE g+1 (Figure 3).  The TPU-native equivalent pins
groves to mesh shards and forwards the queue entry {Input Payload,
Probability Array, hops, grove index} with ``jax.lax.ppermute`` — the
handshake becomes a neighbor-only collective, the cheapest traffic pattern
on a torus (no all-to-all, no all-gather; each hop crosses one ICI link).

Grove placement is STRIDED: with n shards and G groves (G % n == 0), shard
s hosts groves {s, s+n, s+2n, ...}.  Grove g+1 therefore always lives on
shard (g+1) % n — one ring step from grove g's shard — so every lane
rotates exactly one neighbor per round regardless of how many groves each
shard holds.  With n == G this degenerates to the classic one-grove-per-PE
ring; with n == 1 the "ring" is a self-permute and the evaluation is
bit-identical to the batched reference path (same starts, same update).

Each shard holds:
  * the node tables of ITS groves (grove-parallel: tables are *partitioned*,
    never replicated or gathered), and
  * a slice of the batch ("its queue") — lanes are placed on the shard that
    owns their start grove.

Confident lanes die in place (their rotation continues but costs no
evaluation energy), matching the ASIC's completed-entry drain.  The per-hop
update is the shared ``kernels.ref.grove_aggregate_ref`` — the same math
every FogEngine backend runs — so hop counts (the energy quantity) are
bit-identical to Algorithm 2's sequential queue semantics.
"""
from __future__ import annotations

from functools import lru_cache

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.grove import GroveCollection
from repro.forest.tree import _traverse
from repro.kernels import ref


def _grove_order(G: int, n_shards: int) -> np.ndarray:
    """Reorder groves so shard s's contiguous block is {s, s+n, s+2n, ...}.

    shard_map partitions dim 0 in contiguous blocks; after this reorder,
    grove g sits on shard g % n at local offset g // n.
    """
    m = G // n_shards
    return np.arange(G).reshape(m, n_shards).T.reshape(-1)


def _eval_block_grove(feature, threshold, leaf, thr_scale, leaf_scale, x,
                      use_kernels: bool):
    """One grove per shard: whole-block bundle eval [b, F] -> [b, C].

    Tables arrive packed (fp32/bf16/int8 + per-tree scales, the shard's
    slice of a ``ForestPack`` ring layout).  ``use_kernels=True`` runs the
    Pallas tree-traversal PE (kernels/tree_traverse.py — packed node tables
    VMEM-resident, dequantized in-kernel, batch tiled); the jnp path
    dequantizes up front and is the oracle-equivalent fallback."""
    if use_kernels:
        from repro.kernels import ops
        b = x.shape[0]
        blk = b if b <= 128 else 128
        while b % blk:
            blk -= 1
        return ops.tree_traverse(feature[0], threshold[0], leaf[0], x,
                                 thr_scale[0], leaf_scale[0], block_b=blk)
    thr, lf = ref.dequantize_tables(threshold[0], leaf[0], thr_scale[0],
                                    leaf_scale[0])
    per_tree = _traverse(feature[0], thr, lf, x)                 # [b, k, C]
    return per_tree.mean(axis=1)


def _eval_gather_grove(feature, threshold, leaf, thr_scale, leaf_scale, x,
                       local_idx):
    """Multiple groves per shard: per-lane gathered bundle eval.

    feature [m, k, nodes]; local_idx [b] selects each lane's grove — the
    same packed gather + dequantize + walk as ``ForestPack.predict_proba``,
    restricted to this shard's table slice."""
    feat = feature[local_idx]
    thr, lf = ref.dequantize_tables(threshold[local_idx], leaf[local_idx],
                                    thr_scale[local_idx],
                                    leaf_scale[local_idx])

    def one(feat_b, thr_b, leaf_b, x_b):
        per_tree = _traverse(feat_b, thr_b, leaf_b, x_b[None])   # [1, k, C]
        return per_tree[0].mean(axis=0)

    return jax.vmap(one)(feat, thr, lf, x)


@lru_cache(maxsize=64)
def make_fog_ring(mesh: Mesh, axis: str, max_hops: int, n_groves: int,
                  use_kernels: bool = False):
    """Build the jitted ring evaluator for ``mesh`` (grove axis = ``axis``).

    Returns fn(feature, threshold, leaf, thr_scale, leaf_scale, x, start,
    thresh, budget) -> (proba, hops) where the packed grove tables
    (strided-reordered, see ``_grove_order``; fp32/bf16/int8 + per-tree
    dequant scales) and the batch are sharded over ``axis``, ``start`` is
    each lane's global start grove (lane already placed on shard
    start % n_shards), and ``thresh`` / ``budget`` are per-lane [B] vectors
    (a lane's confidence gate and hop budget travel with its queue entry —
    every queue field of the ASIC handshake, including the QoS contract,
    crosses the same ICI link).
    """
    n_shards = mesh.shape[axis]
    assert n_groves % n_shards == 0, (n_groves, n_shards)

    def ring(feature, threshold, leaf, thr_scale, leaf_scale, x, start,
             thresh, budget):
        # Per-shard views: feature [m, k, nodes], x [b, F], start [b].
        b = x.shape[0]
        m = feature.shape[0]
        prob = jnp.zeros((b, leaf.shape[-1]), jnp.float32)
        hops = jnp.zeros((b,), jnp.int32)
        live = jnp.ones((b,), bool)
        gidx = start                          # lane's current global grove
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

        def body(carry, _):
            x, prob, hops, live, gidx, thresh, budget = carry
            if m == 1:
                contrib = _eval_block_grove(feature, threshold, leaf,
                                            thr_scale, leaf_scale, x,
                                            use_kernels)
            else:
                contrib = _eval_gather_grove(feature, threshold, leaf,
                                             thr_scale, leaf_scale, x,
                                             gidx // n_shards)
            prob, hops, live, _ = ref.grove_aggregate_ref(
                prob, contrib, live, hops, thresh)
            live = live & (hops < budget)     # per-lane energy cap
            # the handshake: rotate queue entries to the next grove's shard
            gidx = (gidx + 1) % n_groves
            carry = tuple(jax.lax.ppermute(v, axis, perm)
                          for v in (x, prob, hops, live, gidx, thresh,
                                    budget))
            return carry, None

        (x, prob, hops, live, gidx, thresh, budget), _ = jax.lax.scan(
            body, (x, prob, hops, live, gidx, thresh, budget), None,
            length=max_hops)
        # after max_hops rotations a lane's state sits max_hops shards
        # downstream of where it entered; rotate it back so the gathered
        # output rows line up with the input batch order (identity permute
        # when n_shards divides max_hops)
        back = [(i, (i - max_hops) % n_shards) for i in range(n_shards)]
        prob = jax.lax.ppermute(prob, axis, back)
        hops = jax.lax.ppermute(hops, axis, back)
        prob_norm = prob / jnp.maximum(hops, 1)[:, None]
        return prob_norm, hops

    gspec = P(axis)  # grove tables partitioned over the ring, dim 0
    fn = jax.shard_map(
        ring, mesh=mesh,
        in_specs=(gspec, gspec, gspec, gspec, gspec,
                  P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )
    # results come back replicated, so the caller's un-permute gather
    # needs no sharding annotation on any mesh (explicit axes included)
    return jax.jit(fn, out_shardings=NamedSharding(mesh, P()))


def reorder_tables(gc: GroveCollection, n_shards: int):
    """Strided-reordered fp32 (feature, threshold, leaf) ready to shard over
    the ring.  Legacy helper: packed callers get the same reorder — scales
    included, any dtype — from ``ForestPack.layout("ring", n_shards)``
    (cached per pack; the engine's TableCache serves it)."""
    order = _grove_order(gc.n_groves, n_shards)
    return gc.feature[order], gc.threshold[order], gc.leaf[order]


def _normalize_tables(tables):
    """Accept a legacy 3-tuple (fp32 tables) or a packed 5-tuple with
    per-tree dequant scales; return the 5-tuple form."""
    if len(tables) == 5:
        return tables
    feature, threshold, leaf = tables
    G, k = feature.shape[:2]
    return (feature, threshold, leaf,
            jnp.ones((G, k, 1), jnp.float32),
            jnp.ones((G, k, 1, 1), jnp.float32))


def ring_eval(gc: GroveCollection, x: jax.Array, start: jax.Array,
              thresh, max_hops: int, mesh: Mesh, axis: str = "grove",
              use_kernels: bool = False, tables=None, hop_budget=None):
    """Run the ring with explicit per-lane start groves.

    ``start`` must contain exactly B/n_shards lanes per residue class
    (start % n_shards) — ``engine.sample_starts`` produces such draws.
    Lanes are placed on their start grove's shard, evaluated, and returned
    in the original batch order.  ``thresh`` and ``hop_budget`` may be
    scalars or per-lane [B] vectors (FogPolicy's mixed-QoS contract);
    ``tables`` is an optional precomputed ring layout — either the legacy
    fp32 3-tuple (``reorder_tables(gc, n_shards)``) or the packed 5-tuple
    with dequant scales (``ForestPack.layout("ring", n_shards)``).
    """
    from repro.core.policy import NO_BUDGET
    B = x.shape[0]
    G = gc.n_groves
    n_shards = mesh.shape[axis]
    if B % n_shards:
        raise ValueError(
            f"batch B={B} must divide over {n_shards} ring shards")
    if not isinstance(start, jax.core.Tracer):
        # each shard's queue slice must be exactly B/n lanes or shard_map's
        # positional split would hand lanes the wrong grove tables
        counts = np.bincount(np.asarray(start) % n_shards,
                             minlength=n_shards)
        if not (counts == B // n_shards).all():
            raise ValueError(
                f"start groves not stratified over {n_shards} shards "
                f"(per-shard lane counts {counts.tolist()}); draw them "
                "with engine.sample_starts(key, B, G, n_shards)")
    feature, threshold, leaf, thr_scale, leaf_scale = _normalize_tables(
        tables if tables is not None else reorder_tables(gc, n_shards))
    thresh = jnp.broadcast_to(jnp.asarray(thresh, jnp.float32), (B,))
    if hop_budget is None:
        hop_budget = NO_BUDGET
    budget = jnp.broadcast_to(jnp.asarray(hop_budget, jnp.int32), (B,))
    # stable sort by owning shard -> contiguous equal-size per-shard queues
    perm = jnp.argsort(start % n_shards, stable=True)
    inv = jnp.argsort(perm)
    fn = make_fog_ring(mesh, axis, max_hops, G, use_kernels=use_kernels)
    proba, hops = fn(feature, threshold, leaf, thr_scale, leaf_scale,
                     x[perm], start[perm], thresh[perm], budget[perm])
    return proba[inv], hops[inv]


def fog_ring_eval(gc: GroveCollection, x: jax.Array, key: jax.Array,
                  thresh, max_hops: int, mesh: Mesh, axis: str = "grove",
                  use_kernels: bool = False):
    """Legacy entry point: draw stratified random starts, run the ring.

    .. deprecated::
        Use ``FogEngine(gc, backend="ring", mesh=mesh).eval(x, key,
        policy=FogPolicy(...))`` — this shim remains for callers that
        manage their own meshes.
    """
    import warnings
    warnings.warn(
        "fog_ring_eval is deprecated; use FogEngine(gc, backend='ring', "
        "mesh=mesh).eval(x, key, policy=FogPolicy(threshold=..., "
        "max_hops=...)) instead",
        DeprecationWarning, stacklevel=2)
    from repro.core.engine import sample_starts
    start = sample_starts(key, x.shape[0], gc.n_groves,
                          mesh.shape[axis])
    return ring_eval(gc, x, start, thresh, max_hops, mesh, axis,
                     use_kernels=use_kernels)

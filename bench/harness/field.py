"""The field every serving cell serves, made on the device from the seed.

Extremely randomized trees (Geurts, Ernst & Wehenkel, Machine Learning 63,
2006; scikit-learn's ``ExtraTreesClassifier`` defaults: ``max_features =
sqrt(F)`` candidate features per node, one uniform threshold each between
the node's smallest and largest value, best Gini split, no bootstrap)
grown level by level for all trees at once in ONE jitted call.

The benchmark makes the field, as a language-model benchmark makes its
weights: the program serves it and the reference evaluates the very same
arrays, so the reference takes nothing the program made.

Tables are complete depth-``d`` trees in heap order (node ``i`` has
children ``2i+1`` / ``2i+2``, a row goes right iff ``x[f] > thr``); a node
that does not split is sealed with ``feature 0, threshold +inf`` and its
class distribution is copied to every leaf below it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit,
                   static_argnames=("n_trees", "depth", "n_classes", "k_feat"))
def _grow(x, y, key, *, n_trees, depth, n_classes, k_feat):
    N, F = x.shape
    T, C, K = n_trees, n_classes, k_feat
    rows = jnp.arange(N)
    trees = jnp.arange(T)[:, None]
    node = jnp.zeros((T, N), jnp.int32)
    alive = jnp.ones((T, 1), bool)
    inherit = jnp.full((T, 1, C), 1.0 / C, jnp.float32)
    yb = jnp.broadcast_to(y, (T, N))
    feats, thrs = [], []
    for level in range(depth):
        nodes = 1 << level
        seg = (trees * nodes + node).reshape(-1)              # [T*N]
        counts = jax.ops.segment_sum(
            jnp.ones((T * N,), jnp.float32), seg * C + yb.reshape(-1),
            num_segments=T * nodes * C).reshape(T, nodes, C)
        total = counts.sum(-1)
        dist = jnp.where((alive & (total > 0))[..., None],
                         counts / jnp.maximum(total, 1.0)[..., None], inherit)
        pure = (counts > 0).sum(-1) <= 1
        can_split = alive & (total >= 2) & ~pure

        k_feat_key, k_thr_key = jax.random.split(jax.random.fold_in(key,
                                                                     level))
        u = jax.random.uniform(k_feat_key, (T, nodes, F))
        _, cand = jax.lax.top_k(u, K)                         # [T,nodes,K]
        cand_s = cand[trees, node]                            # [T,N,K]
        v = x[rows[None, :, None], cand_s]                    # [T,N,K]
        lo = jax.ops.segment_min(v.reshape(T * N, K), seg,
                                 num_segments=T * nodes).reshape(T, nodes, K)
        hi = jax.ops.segment_max(v.reshape(T * N, K), seg,
                                 num_segments=T * nodes).reshape(T, nodes, K)
        cut = lo + jax.random.uniform(k_thr_key, (T, nodes, K)) * (hi - lo)
        right = v > cut[trees, node]                          # [T,N,K]
        seg_k = (seg[:, None] * K + jnp.arange(K)[None, :]) * C \
            + yb.reshape(-1, 1)
        left = jax.ops.segment_sum(
            (~right).reshape(T * N, K).astype(jnp.float32).reshape(-1),
            seg_k.reshape(-1),
            num_segments=T * nodes * K * C).reshape(T, nodes, K, C)
        n_l = left.sum(-1)
        n_r = total[..., None] - n_l
        rc = counts[:, :, None, :] - left
        child = (n_l - (left * left).sum(-1) / jnp.maximum(n_l, 1.0)
                 + n_r - (rc * rc).sum(-1) / jnp.maximum(n_r, 1.0))
        parent = total - (counts * counts).sum(-1) / jnp.maximum(total, 1.0)
        gain = parent[..., None] - child
        valid = (n_l >= 1) & (n_r >= 1) & (hi > lo)
        gain = jnp.where(valid, gain, -jnp.inf)
        best = jnp.argmax(gain, axis=-1)                      # [T,nodes]
        ok = can_split & (jnp.take_along_axis(gain, best[..., None],
                                              -1)[..., 0] > 1e-6)
        f_l = jnp.where(ok, jnp.take_along_axis(cand, best[..., None],
                                                -1)[..., 0], 0)
        t_l = jnp.where(ok, jnp.take_along_axis(cut, best[..., None],
                                                -1)[..., 0], jnp.inf)
        feats.append(f_l.astype(jnp.int32))
        thrs.append(t_l.astype(jnp.float32))
        go = x[rows[None, :], f_l[trees, node]] > t_l[trees, node]
        node = 2 * node + go.astype(jnp.int32)
        alive = jnp.repeat(ok, 2, axis=1)
        inherit = jnp.repeat(dist, 2, axis=1)

    leaves = 1 << depth
    seg = (trees * leaves + node).reshape(-1)
    counts = jax.ops.segment_sum(
        jnp.ones((T * N,), jnp.float32), seg * C + yb.reshape(-1),
        num_segments=T * leaves * C).reshape(T, leaves, C)
    total = counts.sum(-1)
    leaf = jnp.where((alive & (total > 0))[..., None],
                     counts / jnp.maximum(total, 1.0)[..., None], inherit)
    return (jnp.concatenate(feats, axis=1), jnp.concatenate(thrs, axis=1),
            leaf)


def grow_field(x_train, y_train, n_classes: int, *, n_trees: int,
               depth: int, seed: int):
    """(feature int32 [T, 2**d-1], threshold float32 [T, 2**d-1],
    leaf float32 [T, 2**d, C]) as device arrays on the default device."""
    F = x_train.shape[1]
    k_feat = max(1, int(np.sqrt(F)))
    return _grow(jnp.asarray(x_train, jnp.float32),
                 jnp.asarray(y_train, jnp.int32), jax.random.key(seed),
                 n_trees=n_trees, depth=depth, n_classes=n_classes,
                 k_feat=k_feat)

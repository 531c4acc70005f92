"""Pallas TPU kernel: the ENTIRE Algorithm-2 loop in one launch.

The paper's PE keeps the grove walk on-chip: an input hops grove-to-grove
without its probability array ever leaving the accelerator.  The per-hop
backends reproduce the semantics but pay a kernel-launch-and-HBM round trip
per hop — ``max_hops x (grove gather + aggregate)`` dispatches, with the
[B, C] probability state re-read from HBM every hop.  This kernel is the
TPU analogue of the PE itself: ALL grove node tables (feature / threshold /
leaf for every grove, every output head) are pinned whole in VMEM, the
batch is tiled over the grid, and the full early-exit loop — per-lane live
mask, per-lane ``[B]`` threshold and hop budget, rotation start
``start [B]``, MaxDiff gate, hop counting, min-over-heads rule — runs as a
``while_loop`` *inside* the kernel.  One launch emits (proba, hops); the
loop exits as soon as every lane in the block is confident (or budgeted
out), so an easy block touches VMEM tables for one hop and stops.

The walk is ``tree_traverse.walk_tree``: batch on lanes, every table read a
one-hot select (see that module for the layouts and why they are exact).
Each lane picks its own grove per hop, so node columns are chosen by a
select chain over the grove masks and leaves by a one-hot over
``(grove, leaf)``.  The gate is ``top2_confidence.maxdiff_lanes``.

Live-lane compaction (``compact=True``): after each hop the block's live
lanes are moved to a contiguous prefix (a stable partition — per-lane
state just relocates, so hops/labels are bit-identical to the uncompacted
walk), and the next hop walks the smallest power-of-two prefix (down to
one 128-lane row) that covers the survivors instead of the full block.
The partition ranks come from one MXU product with a triangular matrix,
and the relocation is a one-hot permutation matmul applied to each 32-bit
word's four bytes (each byte is exact in bf16), so any payload — ±inf and
NaN inputs included — moves bit for bit.  The engine's autotuner measures
compaction on/off per (precision, field size) and serves the faster
setting.

Block sizing: batch blocks are whole 128-lane rows.  The resident tables
dominate VMEM in their padded kernel layout — the whole field of groves,
not one grove, must fit.  The wrapper rejects working sets over
``VMEM_BUDGET`` with a ValueError reporting required vs available bytes and
the two remedies (``chunk_b=...`` batch slices, ``precision="int8"``
tables); the engine's ``chunk_b="auto"`` applies the first remedy.

Batches need not align: the batch is dead-lane padded to the block boundary
(padded lanes enter with live=0, so they never walk, never count hops, and
never keep the early-exit loop spinning) and outputs are sliced back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.top2_confidence import maxdiff_lanes
from repro.kernels.tree_traverse import (
    LANE, SELECT_ROWS, VMEM_BUDGET, compiler_params, feature_rows,
    lane_block, leaf_rows, leaf_table_bytes, node_columns, node_table_bytes,
    resolve_interpret, round_up, vmem_error, walk_lane_bytes, walk_tree,
    x_columns)

# batch blocks are sized in multiples of this (one vreg row of lanes);
# fit_block_b rounds down to it
LANE_ALIGN = LANE

# smallest compacted walk prefix: one vreg row of lanes
MIN_COMPACT_WIDTH = LANE

# per-lane [1, w] rows the kernel carries: live, hops, start, budget,
# compaction origin, threshold
_LANE_ROWS = 6


def _field_dims(feature, leaf):
    O, G, t, _ = feature.shape
    L, C = leaf.shape[3], leaf.shape[4]
    return O, G, t, int(np.log2(L) + 0.5), C


def vmem_table_bytes(feature, threshold, leaf, thr_scale, leaf_scale) -> int:
    """VMEM bytes of the tables in the kernel's layout: the fp32 node
    columns and the packed leaf rows, padded to whole VMEM tiles (the
    per-tree scales live in SMEM)."""
    O, G, t, depth, C = _field_dims(feature, leaf)
    return (node_table_bytes(O * t * G, feature.shape[3])
            + leaf_table_bytes(O * t, C, G << depth, leaf.dtype.itemsize))


def vmem_lane_bytes(*, n_heads: int, n_classes: int, n_features: int,
                    block_b: int, compact: bool = True) -> int:
    """Per-lane VMEM: double-buffered input/output blocks (``x^T`` column,
    [O, C] probabilities, the per-lane rows each padded to an 8-row tile),
    the working copy of ``x^T``, the loop-carried state, one walk's
    temporaries and — with compaction — the [block_b, block_b] bf16
    permutation and prefix-count matrices."""
    xf = feature_rows(n_features)
    oc = n_heads * round_up(n_classes, 8)
    blocks = 2 * 4 * (xf + oc + 5 * 8)
    carry = 4 * (xf + 2 * oc + _LANE_ROWS * 8)
    walk = walk_lane_bytes(n_classes=n_classes)
    perm = 2 * 2 * block_b if compact else 0
    return blocks + carry + walk + perm


def vmem_working_set(feature, threshold, leaf, thr_scale, leaf_scale, *,
                     block_b: int, n_features: int,
                     compact: bool = True) -> int:
    """Bytes resident in VMEM: every table + one batch block's state."""
    O, _, _, _, C = _field_dims(feature, leaf)
    tables = vmem_table_bytes(feature, threshold, leaf, thr_scale, leaf_scale)
    return tables + block_b * vmem_lane_bytes(
        n_heads=O, n_classes=C, n_features=n_features, block_b=block_b,
        compact=compact)


def fit_block_b(feature, threshold, leaf, thr_scale, leaf_scale, *,
                n_features: int) -> int:
    """Largest LANE_ALIGN-multiple batch block whose working set (with
    compaction, the larger footprint) stays under the VMEM budget beside
    the tables — 0 when not even one 128-lane row fits.  ``FogEngine``'s
    auto-chunking and the autotuner's analytic seed size from this."""
    fit = 0
    b = LANE_ALIGN
    while vmem_working_set(feature, threshold, leaf, thr_scale, leaf_scale,
                           block_b=b, n_features=n_features) < VMEM_BUDGET:
        fit = b
        b += LANE_ALIGN
    return fit


def walk_widths(block: int, compact: bool) -> list[int]:
    """The lane widths one hop of a ``block``-lane block may walk, widest
    first: the whole block, and with compaction its power-of-two prefixes
    down to ``MIN_COMPACT_WIDTH``."""
    widths = [block]
    if compact:
        while widths[-1] % 2 == 0 and widths[-1] // 2 >= MIN_COMPACT_WIDTH:
            widths.append(widths[-1] // 2)
    return widths


def walked_lane_hops(hops, block: int, compact: bool) -> int:
    """Lane-hops the kernel walked for a batch whose lanes took ``hops``
    (``[B]``, dead-lane padded here as in the kernel): each block loops
    until its last live lane stops, and each hop walks the narrowest of
    :func:`walk_widths` that covers the lanes still live (the whole block
    without compaction), used lanes or not."""
    h = np.asarray(hops, np.int64)
    h = np.pad(h, (0, (-h.size) % block)).reshape(-1, block)
    if not h.size or not h.max():
        return 0
    # live[b, j]: lanes of block b still live on its hop j (hops > j)
    live = (h[:, :, None] > np.arange(h.max())).sum(axis=1)
    widths = np.asarray(walk_widths(block, compact))
    narrowest = widths[(widths >= live[..., None]).sum(axis=-1) - 1]
    return int(np.where(live > 0, narrowest, 0).sum())


def _bytes_of(a):
    bits = a if a.dtype == jnp.int32 else jax.lax.bitcast_convert_type(
        a, jnp.int32)
    return [((bits >> s) & 0xFF).astype(jnp.float32).astype(jnp.bfloat16)
            for s in (0, 8, 16, 24)]


def _perm_matrix(dest):
    """``pt[i, k] = (dest[k] == i)``: the one-hot matrix that moves lane k
    of a ``[R, w]`` array to lane ``dest[k]``."""
    w = dest.shape[1]
    return (jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
            == dest).astype(jnp.bfloat16)


def _relocate(arrays, pt):
    """Move every ``[R, w]`` 32-bit array's lanes by ``pt``.

    One one-hot permutation matmul per byte of each word: a byte is exact
    in bf16 and lands alone in its output cell, so the words reassemble
    bit for bit whatever they hold."""
    out = []
    for a in arrays:
        word = None
        for s, byte in zip((0, 8, 16, 24), _bytes_of(a)):
            moved = jax.lax.dot_general(
                byte, pt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(jnp.int32) << s
            word = moved if word is None else word | moved
        out.append(word if a.dtype == jnp.int32
                   else jax.lax.bitcast_convert_type(word, a.dtype))
    return out


def _compact_dest(live):
    """Stable partition of ``live [1, w]`` (int32 0/1): live lanes to the
    front in order, dead lanes after them in order.  Returns each lane's
    destination; the live-before counts come from one exact bf16 matmul
    with a strictly-lower-triangular ones matrix (counts < 2**24)."""
    w = live.shape[1]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
           < jax.lax.broadcasted_iota(jnp.int32, (w, w), 1))
    before = jnp.dot(live.astype(jnp.float32).astype(jnp.bfloat16),
                     tri.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    n_live = jnp.sum(live)
    return jnp.where(live > 0, before, n_live + lane - before)


def _fused_fog_kernel(tab_ref, leaf_ref, scale_ref, x_ref, start_ref,
                      thresh_ref, budget_ref, live_ref, proba_out, hops_out,
                      xs_ref, *, depth: int, max_hops: int, n_groves: int,
                      n_heads: int, grove_size: int, n_features: int,
                      compact: bool, quant: bool):
    O, t, G = n_heads, grove_size, n_groves
    K = O * t * G
    C = leaf_ref.shape[1]
    BB = x_ref.shape[1]
    xs_ref[...] = x_ref[...]             # [F, BB] working copy, batch on lanes

    def contrib(g, w):
        # each head's grove mean over its t trees, for the lanes' groves g
        gmasks = [g == gi for gi in range(G)]
        out = []
        for o in range(O):
            total = None
            for j in range(t):
                d = walk_tree(tab_ref, leaf_ref, scale_ref, xs_ref, w, gmasks,
                              tree=o * t + j, col=(o * t + j) * G, n_cols=K,
                              depth=depth, n_features=n_features,
                              quant=quant)
                total = d if total is None else total + d
            out.append(total / t)
        return tuple(out)

    # Only one branch executes per hop (lax.switch); survivors always sit in
    # a prefix after compaction, so the smallest width covering them is exact.
    widths = walk_widths(BB, compact)

    def contrib_all(g, n_live):
        if len(widths) == 1:
            return contrib(g, BB)

        def prefix_branch(w):
            def run(g):
                out = contrib(g[:, :w], w)
                if w == BB:
                    return out
                # lanes beyond the prefix are dead (livef = 0 masks them)
                pad = jnp.zeros((C, BB - w), jnp.float32)
                return tuple(jnp.concatenate([d, pad], axis=1) for d in out)
            return run

        lvl = jnp.zeros((), jnp.int32)
        for w in widths[1:]:
            lvl = lvl + (n_live <= w).astype(jnp.int32)
        return jax.lax.switch(lvl, [prefix_branch(w) for w in widths], g)

    def do_compact(ops):
        probs, live, hops, start, budget, orig, thresh = ops
        pt = _perm_matrix(_compact_dest(live))

        def move_x(k, carry):
            r0 = pl.multiple_of(k * SELECT_ROWS, SELECT_ROWS)
            rows = xs_ref[pl.ds(r0, SELECT_ROWS), :]
            xs_ref[pl.ds(r0, SELECT_ROWS), :] = _relocate([rows], pt)[0]
            return carry

        jax.lax.fori_loop(0, xs_ref.shape[0] // SELECT_ROWS, move_x, 0)
        rows = jnp.concatenate(
            [live, hops, start, budget, orig,
             jax.lax.bitcast_convert_type(thresh, jnp.int32)], axis=0)
        moved = _relocate([*probs, rows], pt)
        rows = moved[-1]
        return (tuple(moved[:O]), rows[0:1], rows[1:2], rows[2:3],
                rows[3:4], rows[4:5],
                jax.lax.bitcast_convert_type(rows[5:6], jnp.float32))

    def body(state):
        j, probs, live, hops, start, budget, orig, thresh = state
        n_live = jnp.sum(live)
        if compact:
            # hop 1 of a full block (and any fully-live block) skips it
            probs, live, hops, start, budget, orig, thresh = jax.lax.cond(
                n_live < BB, do_compact, lambda ops: ops,
                (probs, live, hops, start, budget, orig, thresh))
        g = (start + j) % G
        livef = live.astype(jnp.float32)
        probs = tuple(p + d * livef
                      for p, d in zip(probs, contrib_all(g, n_live)))
        hops = hops + live
        margin = None
        for p in probs:                  # min-over-outputs rule
            m = maxdiff_lanes(p / jnp.maximum(hops, 1).astype(jnp.float32))
            margin = m if margin is None else jnp.minimum(margin, m)
        live = ((live > 0) & (margin < thresh)
                & (hops < budget)).astype(jnp.int32)
        return j + 1, probs, live, hops, start, budget, orig, thresh

    def cond(state):
        return (state[0] < max_hops) & (jnp.sum(state[2]) > 0)

    state0 = (jnp.zeros((), jnp.int32),
              tuple(jnp.zeros((C, BB), jnp.float32) for _ in range(O)),
              live_ref[...], jnp.zeros((1, BB), jnp.int32), start_ref[...],
              budget_ref[...], jax.lax.broadcasted_iota(jnp.int32, (1, BB), 1),
              thresh_ref[...])
    _, probs, _, hops, _, _, orig, _ = jax.lax.while_loop(cond, body, state0)
    if compact:
        # undo the accumulated compaction: row i holds input lane orig[i]
        moved = _relocate([*probs, hops], _perm_matrix(orig))
        probs, hops = moved[:O], moved[O]
    denom = jnp.maximum(hops, 1).astype(jnp.float32)
    for o in range(O):
        proba_out[o] = probs[o] / denom
    hops_out[...] = hops


def fused_fog_pallas(feature: jax.Array, threshold: jax.Array,
                     leaf: jax.Array, x: jax.Array, start: jax.Array,
                     thresh: jax.Array, budget: jax.Array,
                     thr_scale: jax.Array | None = None,
                     leaf_scale: jax.Array | None = None, *,
                     max_hops: int, block_b: int = 128,
                     compact: bool = True,
                     interpret: bool | None = None):
    """One-launch Algorithm-2 evaluation over head-stacked packed tables.

    feature    int32           [O, G, t, 2**d - 1]   all heads, all groves
    threshold  fp32|bf16|int8  [O, G, t, 2**d - 1]
    leaf       fp32|bf16|int8  [O, G, t, 2**d, C]
    thr_scale  float32         [O, G, t, 1]      per-tree dequant scales
    leaf_scale float32         [O, G, t, 1, 1]   (default ones)
    x          float32 [B, F];  start int32 [B];  thresh float32 [B];
    budget     int32   [B]
    block_b    batch lanes per grid block, rounded up to whole 128-lane rows
    compact    move live lanes to a prefix each hop and walk only the
               covering power-of-two prefix (bit-identical results)
    interpret  None derives from ``jax.default_backend()`` (compiled on a
               real TPU, interpreted elsewhere); a bool overrides
    returns    (proba float32 [B, O, C] hop-normalized, hops int32 [B])
    """
    B, F = x.shape
    O, G, t, depth, C = _field_dims(feature, leaf)
    K = O * t * G
    block_b = lane_block(block_b, B)
    interpret = resolve_interpret(interpret)
    if thr_scale is None:
        thr_scale = jnp.ones((O, G, t, 1), jnp.float32)
    if leaf_scale is None:
        leaf_scale = jnp.ones((O, G, t, 1, 1), jnp.float32)

    ws = vmem_working_set(feature, threshold, leaf, thr_scale, leaf_scale,
                          block_b=block_b, n_features=F, compact=compact)
    if ws >= VMEM_BUDGET:
        tables = vmem_table_bytes(feature, threshold, leaf, thr_scale,
                                  leaf_scale)
        raise vmem_error(
            "fused FoG", ws,
            f"{O} heads x {G} groves x {t} trees, depth {depth}, {C} "
            f"classes, {threshold.dtype} tables = {tables} B resident + "
            f"block_b={block_b} batch state = {ws - tables} B; the largest "
            f"batch block fitting beside these tables is "
            f"{fit_block_b(feature, threshold, leaf, thr_scale, leaf_scale, n_features=F)}",
            chunkable=True)

    # kernel layouts: node columns / scales ordered (head, tree, grove),
    # leaf rows per (head, tree) with lanes (grove, leaf)
    def per_tree(a):
        return jnp.swapaxes(a, 1, 2)                 # [O, t, G, ...]

    tab = node_columns(per_tree(feature).reshape(K, -1),
                       per_tree(threshold).reshape(K, -1))
    leaves = leaf_rows(per_tree(leaf).reshape(O * t, G, 1 << depth, C))
    scales = jnp.stack([per_tree(thr_scale).reshape(K),
                        per_tree(leaf_scale).reshape(K)]).astype(jnp.float32)

    Bp = round_up(B, block_b)
    pad = Bp - B                   # padded lanes enter dead and are sliced off
    row = lambda v, dt, fill=0: jnp.pad(
        jnp.asarray(v, dt), (0, pad), constant_values=fill).reshape(1, Bp)
    live = row(jnp.ones((B,), jnp.int32), jnp.int32)

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)     # whole, single copy
    lanes = pl.BlockSpec((1, block_b), lambda i: (0, i))
    proba, hops = pl.pallas_call(
        functools.partial(_fused_fog_kernel, depth=depth, max_hops=max_hops,
                          n_groves=G, n_heads=O, grove_size=t,
                          n_features=F, compact=compact,
                          quant=threshold.dtype == jnp.int8),
        grid=(Bp // block_b,),
        in_specs=[vmem, vmem, pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((feature_rows(F), block_b),
                               lambda i: (0, i)),
                  lanes, lanes, lanes, lanes],
        out_specs=[pl.BlockSpec((O, C, block_b), lambda i: (0, 0, i)),
                   lanes],
        out_shape=[jax.ShapeDtypeStruct((O, C, Bp), jnp.float32),
                   jax.ShapeDtypeStruct((1, Bp), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((feature_rows(F), block_b), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(tab, leaves, scales, x_columns(x, Bp), row(start, jnp.int32),
      row(thresh, jnp.float32), row(budget, jnp.int32, 1), live)
    return jnp.transpose(proba, (2, 0, 1))[:B], hops[0, :B]

"""Pallas TPU kernel: level-wise split-search histograms for the trainer.

The device forest trainer (``forest/grow.py``) reduces each level of tree
induction to one tensor: per-(tree, node, feature, bin, class) weighted
sample counts, from which every candidate split's gain falls out of a
cumsum.  This module builds that tensor two ways behind one dispatcher:

``histogram_level_pallas``
    The TPU-native scatter-add: a batch-tiled Pallas kernel whose
    histogram block stays VMEM-resident across the batch grid dimension
    (zero-initialized on the first tile, accumulated in fp32 on every
    revisit).  Data-dependent vector scatter does not vectorize on the
    VPU, so the scatter is expressed as the classic one-hot contraction —
    rows one-hot in (node, class) weighted by the bootstrap multiplicity,
    columns one-hot in (feature, bin), accumulated with one MXU matmul per
    tile.  The tree axis rides the leading grid dimension (equivalently a
    vmap: it would add the same leading grid dim).  Per-sample rows arrive
    as ``[1, block_n]`` lane rows of ``[T, 1, N]`` arrays, and the flat
    (feature, bin) column axis is tiled in whole 128-lane blocks; each
    column's feature is found by an exact one-hot matmul against the bins
    block instead of a reshape.  Both matmuls run at HIGHEST precision, so
    the weighted counts are exact whatever the weights.

``histogram_level_scatter``
    The XLA path: the SAME one-hot contraction, but as a row-wise
    segment-sum — each sample contributes its precomputed weighted
    (feature, bin) one-hot row (``F*bins`` contiguous floats, built once
    per fit since bins never change across levels) to the ``node*C + y``
    segment.  One window update per sample instead of ``F`` scalar
    scatters amortizes the scatter overhead ~F-fold, and the flops stay
    O(N*F*bins) at every level — deep levels have many nodes, each holding
    few samples, which is exactly where a dense one-hot matmul wastes its
    width.  Exact same fp32 counts as the kernel (integer-valued sums, no
    rounding).

``histogram_level`` picks between them by the level's row count
``nodes * n_classes`` against a crossover threshold; the autotuner
(``kernels/autotune.py``) measures the crossover and the tile sizes per
(n_trees, depth, F, bins, C) signature and persists them in the same
best-config cache the serving engine consults.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tree_traverse import (LANE, VMEM_BUDGET, compiler_params,
                                         lane_block, resolve_interpret,
                                         round_up, vmem_error)

# default tile sizes (the analytic seed; autotune may override)
BLOCK_N = 1024      # batch lanes per tile (a multiple of 128)
BLOCK_R = 512       # (node, class) histogram rows resident per block
BLOCK_COLS = 512    # target (feature, bin) columns per block


def default_block_f(n_features: int, n_bins: int) -> int:
    """Features per column block: as many as keep the one-hot/bin block
    near BLOCK_COLS columns (floor 1 so any signature is runnable)."""
    return max(1, min(n_features, BLOCK_COLS // max(n_bins, 1)))


def _hist_kernel(node_ref, y_ref, w_ref, bins_ref, out_ref, *,
                 n_classes: int, n_bins: int, block_r: int, block_c: int):
    ir = pl.program_id(1)
    jc = pl.program_id(2)
    ib = pl.program_id(3)
    node = node_ref[0]              # [1, BN] level-local node ids
    y = y_ref[...]                  # [1, BN]
    w = w_ref[0]                    # [1, BN] bootstrap multiplicity (0 = OOB/pad)
    bins = bins_ref[...]            # [BN, F] bin indices
    BN, F = bins.shape

    # rows: one-hot in (node, class), weighted — local to this row block
    row = node * n_classes + y - ir * block_r
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (block_r, BN), 0)
    a = jnp.where(r_iota == row, w, 0.0)                    # [block_r, BN]

    # cols: one-hot in the flat (feature, bin) index c = f * n_bins + bin.
    # Each column's feature f(c) picks the sample's global column
    # f(c) * n_bins + bins[n, f(c)] through an exact one-hot matmul
    c0 = jc * block_c
    cf = jax.lax.broadcasted_iota(jnp.int32, (F, block_c), 1) + c0
    lo = jax.lax.broadcasted_iota(jnp.int32, (F, block_c), 0) * n_bins
    pick = ((lo <= cf) & (cf < lo + n_bins)).astype(jnp.float32)
    gcol = (bins.astype(jnp.float32) + n_bins * jax.lax.broadcasted_iota(
        jnp.int32, (BN, F), 1).astype(jnp.float32))
    gcol = jnp.dot(gcol, pick, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)      # [BN, block_c]
    cn = jax.lax.broadcasted_iota(jnp.int32, (BN, block_c), 1) + c0
    b = (gcol == cn.astype(jnp.float32)).astype(jnp.float32)

    part = jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)

    @pl.when(ib == 0)       # first batch tile zero-inits the resident block
    def _():
        out_ref[0] = jnp.zeros_like(out_ref[0])

    out_ref[0] += part


def hist_vmem(*, block_n: int, block_r: int, block_c: int,
              n_features: int) -> int:
    """Modeled VMEM bytes of one grid step: the weighted row one-hot, the
    double-buffered bins block and its fp32 copy, the column pick matrix,
    the column one-hot and its global-index operand, the partial and the
    double-buffered resident histogram block, the per-sample rows (each
    padded to an 8-row tile, double-buffered)."""
    f = n_features
    return 4 * (block_r * block_n + 3 * block_n * f + f * block_c
                + 2 * block_n * block_c + 3 * block_r * block_c
                + 2 * 3 * 8 * block_n)


def histogram_level_pallas(node: jax.Array, y: jax.Array, w: jax.Array,
                           bins: jax.Array, *, n_nodes: int, n_bins: int,
                           n_classes: int, block_n: int = BLOCK_N,
                           block_r: int = BLOCK_R, block_f: int | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """[T,N] node ids x [N] labels x [T,N] weights x [N,F] bins ->
    [T, n_nodes, F, n_bins, C] fp32 count histograms.

    The batch (grid dim 3, innermost) is dead-padded with w=0 lanes; rows
    (node*C + y) and the flat (feature, bin) columns are padded to their
    block sizes and sliced off the output.  ``block_f`` features' worth of
    columns, rounded up to whole 128-lane blocks, make one column block.
    Weighted counts are integer-valued, so fp32 accumulation is exact below
    2**24 samples per cell.
    """
    T, N = node.shape
    F = bins.shape[1]
    if block_f is None:
        block_f = default_block_f(F, n_bins)
    R = n_nodes * n_classes
    block_r = min(block_r, R)
    if block_r < R:
        block_r = round_up(block_r, 8)
    block_n = lane_block(block_n, N)
    cols = F * n_bins
    block_c = min(round_up(min(block_f, F) * n_bins, LANE),
                  round_up(cols, LANE))

    need = hist_vmem(block_n=block_n, block_r=block_r, block_c=block_c,
                     n_features=F)
    if need >= VMEM_BUDGET:
        raise vmem_error(
            "histogram", need,
            f"block_n={block_n} x block_r={block_r} rows x "
            f"{block_c} (feature, bin) cols")

    Np = round_up(N, block_n)
    Rp = round_up(R, block_r)
    Cp = round_up(cols, block_c)
    pad_n = Np - N
    node = jnp.pad(node.astype(jnp.int32), ((0, 0), (0, pad_n)))[:, None]
    y = jnp.pad(y.astype(jnp.int32), (0, pad_n))[None]
    w = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, pad_n)))[:, None]
    bins = jnp.pad(bins.astype(jnp.int32), ((0, pad_n), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_hist_kernel, n_classes=n_classes, n_bins=n_bins,
                          block_r=block_r, block_c=block_c),
        grid=(T, Rp // block_r, Cp // block_c, Np // block_n),
        in_specs=[
            pl.BlockSpec((1, 1, block_n), lambda t, ir, jc, ib: (t, 0, ib)),
            pl.BlockSpec((1, block_n), lambda t, ir, jc, ib: (0, ib)),
            pl.BlockSpec((1, 1, block_n), lambda t, ir, jc, ib: (t, 0, ib)),
            pl.BlockSpec((block_n, F), lambda t, ir, jc, ib: (ib, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_r, block_c),
                               lambda t, ir, jc, ib: (t, ir, jc)),
        out_shape=jax.ShapeDtypeStruct((T, Rp, Cp), jnp.float32),
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(node, y, w, bins)

    out = out[:, :R, :cols]
    out = out.reshape(T, n_nodes, n_classes, F, n_bins)
    return jnp.transpose(out, (0, 1, 3, 4, 2))      # [T, nodes, F, B, C]


def onehot_rows(bins: jax.Array, w: jax.Array, n_bins: int) -> jax.Array:
    """The level-invariant half of the scatter path, built once per fit:
    ``wu[t, n] = w[t, n] * onehot(bins[n])`` — each sample's weighted
    (feature, bin) one-hot row ``[F * n_bins]``, shared by every level's
    segment-sum."""
    N, F = bins.shape
    u = (bins.astype(jnp.int32)[:, :, None]
         == jnp.arange(n_bins)).astype(jnp.float32)
    u = u.reshape(N, F * n_bins)
    return w.astype(jnp.float32)[:, :, None] * u[None]


def histogram_level_scatter(node: jax.Array, y: jax.Array, w: jax.Array,
                            bins: jax.Array, *, n_nodes: int, n_bins: int,
                            n_classes: int,
                            wu: jax.Array | None = None) -> jax.Array:
    """XLA segment-sum path: identical [T, n_nodes, F, n_bins, C] counts
    via one ``F * n_bins``-row window update per sample per tree.

    ``wu`` is the precomputed :func:`onehot_rows` output; pass it when
    calling once per level (the trainer does) so the one-hot rows are
    built once per fit instead of once per level."""
    T, N = node.shape
    F = bins.shape[1]
    if wu is None:
        wu = onehot_rows(bins, w, n_bins)
    seg = node.astype(jnp.int32) * n_classes + y.astype(jnp.int32)[None, :]

    def one(seg_t, wu_t):
        return jax.ops.segment_sum(wu_t, seg_t,
                                   num_segments=n_nodes * n_classes)

    out = jax.vmap(one)(seg, wu)                 # [T, nodes*C, F*n_bins]
    out = out.reshape(T, n_nodes, n_classes, F, n_bins)
    return jnp.transpose(out, (0, 1, 3, 4, 2))   # [T, nodes, F, B, C]


def histogram_level(node: jax.Array, y: jax.Array, w: jax.Array,
                    bins: jax.Array, *, n_nodes: int, n_bins: int,
                    n_classes: int, matmul_max_r: int = 0,
                    block_n: int = BLOCK_N, block_r: int = BLOCK_R,
                    block_f: int | None = None,
                    interpret: bool | None = None,
                    wu: jax.Array | None = None) -> jax.Array:
    """The trainer's per-level histogram: the Pallas one-hot kernel while
    the level's ``n_nodes * n_classes`` row count is at most
    ``matmul_max_r`` (few wide nodes — matmul territory), else the XLA
    segment-sum path (many thin nodes).  Both produce identical counts;
    the crossover and tile sizes come from ``kernels.autotune``."""
    if n_nodes * n_classes <= matmul_max_r:
        return histogram_level_pallas(
            node, y, w, bins, n_nodes=n_nodes, n_bins=n_bins,
            n_classes=n_classes, block_n=block_n, block_r=block_r,
            block_f=block_f, interpret=interpret)
    return histogram_level_scatter(node, y, w, bins, n_nodes=n_nodes,
                                   n_bins=n_bins, n_classes=n_classes,
                                   wu=wu)

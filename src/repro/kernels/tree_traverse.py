"""Pallas TPU kernel: grove bundle tree traversal.

The paper's PE — a comparator array walking k decision trees — becomes a
VMEM-resident walk: the grove's node tables and leaf distributions are
pinned whole in VMEM, the batch is tiled over the grid, and the depth loop
is fully unrolled (d is static).

Layout (what the TPU compiler accepts): the batch rides the LANE axis.
Every per-lane quantity is a ``[rows, w]`` tile — the input block arrives
transposed as ``x^T [F, w]``, a lane's walk index is a ``[1, w]`` row — and
the kernel never gathers.  Mosaic lowers no data-dependent gather across
sublanes or into a 4-D table, so each table read is a one-hot select:

* node tables (feature index + threshold) live as fp32 COLUMNS
  ``[nodes, 2K]`` (one column per tree, feature columns then threshold
  columns).  A level's nodes are a static row range; each lane's node is
  picked by ``iota == idx`` and a sublane sum of the selected column — a
  sum of one value and zeros, so the threshold (±inf sentinels included)
  comes back bit-exact.  Per-lane grove choice (the fused kernel) is a
  select chain over the grove columns.
* the input value ``x[f]`` is the same select over the ``[F, w]`` block.
* leaf distributions live as ``[K, C, leaves]`` rows, are widened (and,
  for int8, dequantized) to fp32 a chunk at a time and picked by an MXU
  one-hot contraction ``leaf [C, l] @ onehot [l, w]`` at
  ``precision=HIGHEST``: a one-hot product is then the fp32 value itself.

Leaves stay at the pack's width in VMEM (fp32 / bf16 / int8 codes); node
columns ride as fp32 (int32 indices below 2**24, bf16 values and int8 codes
are all exactly fp32-representable).  int8 codes dequantize after the
select with the per-tree fp32 scales, which live in SMEM.

Row ranges wider than ``SELECT_ROWS`` are selected in chunks so the one-hot
temporaries stay bounded however deep the trees are; the VMEM model counts
the padded table layout plus those chunks, and the same limit is handed to
the compiler, so a working set the model admits is one Mosaic accepts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# usable VMEM budget shared by every table-resident kernel: what the VMEM
# model may hand out.  The compiler is given VMEM_LIMIT (v5e's scoped
# default), the budget plus headroom for Mosaic's own spills.
VMEM_BUDGET = 14 * 2**20
VMEM_LIMIT = 16 * 2**20

# batch lanes per vreg row: every batch block is a multiple of this
LANE = 128

# rows of one one-hot select step: node levels, the feature axis and the
# leaf axis are all walked in chunks of this many rows inside fori_loops,
# so the select temporaries stay a few vregs deep however wide the field
SELECT_ROWS = 64
LEAF_ROWS = 256


def round_up(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def lane_block(block_b: int, B: int) -> int:
    """The batch block the kernels run: ``block_b`` rounded up to whole
    128-lane rows, and no wider than the lane-padded batch."""
    return min(round_up(max(int(block_b), 1), LANE), round_up(B, LANE))


def compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)


def resolve_interpret(override: bool | None = None) -> bool:
    """The one place the Pallas ``interpret`` flag is decided.

    ``None`` (the default everywhere) derives it from the runtime: anything
    but a real TPU backend runs the kernel body interpreted as jnp (Mosaic
    cannot target CPU/GPU here), while a TPU compiles to Mosaic — so a
    real-TPU deployment never silently serves the interpreted kernel.  An
    explicit bool wins unconditionally (debugging a Mosaic miscompile with
    ``interpret=True`` on TPU, or asserting compiled execution in tests).
    """
    if override is not None:
        return bool(override)
    return jax.default_backend() != "tpu"


def vmem_error(kind: str, required: int, detail: str,
               chunkable: bool = False) -> ValueError:
    """The shared over-budget rejection: required vs available bytes, plus
    the remedies.  ``chunkable`` names auto-chunking only where the engine
    actually applies it (the fused backend); the per-grove kernel's budget
    is dominated by its resident tables, which chunking cannot shrink."""
    chunk = ("evaluate in slices that fit (FogPolicy(chunk_b=\"auto\") "
             "sizes them from the pack footprint), or " if chunkable else "")
    return ValueError(
        f"{kind} VMEM working set is {required} B ({required / 2**20:.1f} "
        f"MiB) but only {VMEM_BUDGET} B ({VMEM_BUDGET / 2**20:.1f} MiB) is "
        f"usable ({detail}); remedies: {chunk}shrink the resident tables "
        "with precision=\"int8\" (~4x smaller than fp32); shrinking "
        "block_b, n_groves, grove_size or depth also helps")


# --------------------------------------------------------------------------
# table layouts and the VMEM model
# --------------------------------------------------------------------------

def _sublanes(itemsize: int) -> int:
    """Rows of one VMEM tile for a dtype (8 x 32-bit, 16 x bf16, 32 x int8)."""
    return 8 * (4 // itemsize)


def node_columns(feature: jax.Array, threshold: jax.Array) -> jax.Array:
    """``[K, nodes]`` feature + threshold tables -> one fp32 column table
    ``[rows, 2K]``: column k is tree k's feature index, column K + k its
    threshold (as stored: fp32/bf16 value or int8 code).  Rows are padded
    so a select step may start at any 8-aligned row below ``nodes``."""
    n = feature.shape[-1]
    tab = jnp.concatenate([feature.T.astype(jnp.float32),
                           threshold.T.astype(jnp.float32)], axis=1)
    return jnp.pad(tab, ((0, node_rows(n) - n), (0, 0)))


def node_rows(n_nodes: int) -> int:
    return round_up(n_nodes, 8) + SELECT_ROWS


def node_table_bytes(n_trees: int, n_nodes: int) -> int:
    return node_rows(n_nodes) * round_up(2 * n_trees, LANE) * 4


def leaf_table_bytes(n_rows: int, n_classes: int, width: int,
                     itemsize: int) -> int:
    return (n_rows * round_up(n_classes, _sublanes(itemsize))
            * round_up(width, LEAF_ROWS) * itemsize)


def feature_rows(n_features: int) -> int:
    """Rows of the transposed input block: whole select steps."""
    return round_up(n_features, SELECT_ROWS)


def walk_lane_bytes(*, n_classes: int) -> int:
    """Per-lane VMEM of one walk's temporaries: a leaf-select step (one-hot
    operand, its bf16 split for the HIGHEST-precision matmul and the
    selected rows), a node/feature select step (mask, two selected columns)
    and the per-tree distribution accumulators."""
    return 4 * (3 * LEAF_ROWS + 4 * SELECT_ROWS + 3 * round_up(n_classes, 8))


# --------------------------------------------------------------------------
# the lane-major walk shared with the fused kernel
# --------------------------------------------------------------------------

def _lane_pick(values, gmasks):
    """Per-lane choice among ``values(g)`` (arrays or scalars) by grove
    mask: a select chain, exact for any payload."""
    out = values(0)
    for g in range(1, len(gmasks) if gmasks else 1):
        out = jnp.where(gmasks[g], values(g), out)
    return out


def _chunked(lo: int, hi: int, step, init):
    """Run ``step(r0, rows, acc)`` over ``[lo, hi)`` in SELECT_ROWS chunks:
    one static step when the range fits, else a fori_loop whose body is
    one fixed-size chunk (ranges start 8-aligned; tables are padded)."""
    if hi - lo <= SELECT_ROWS:
        return step(lo, round_up(hi - lo, 8), init)
    n = -(-(hi - lo) // SELECT_ROWS)
    return jax.lax.fori_loop(
        0, n, lambda k, acc: step(
            pl.multiple_of(lo + k * SELECT_ROWS, 8), SELECT_ROWS, acc), init)


def walk_tree(tab_ref, leaf_ref, scale_ref, x_ref, w: int, gmasks, *,
              tree: int, col: int, n_cols: int, depth: int, n_features: int,
              quant: bool):
    """One tree position's walk for the first ``w`` lanes -> ``[C, w]``.

    tab_ref    [rows, 2 * n_cols] fp32 node columns; lane grove g reads
               column ``col + g`` (feature) / ``n_cols + col + g`` (threshold)
    leaf_ref   [n_trees, C, width] packed leaves; row ``tree``, lanes
               ``g * leaves + leaf``
    scale_ref  SMEM [2, n_cols] per-tree threshold / leaf scales (int8)
    x_ref      [feature_rows(F), >= w] fp32 inputs, batch on lanes
    gmasks     per-grove ``[1, w]`` lane masks, or None for one grove
    """
    C = leaf_ref.shape[1]
    n_leaves = 1 << depth
    zero = jnp.zeros((1, w), jnp.float32)

    def node_step(idx):
        def step(r0, rows, acc):
            hit = (jax.lax.broadcasted_iota(jnp.int32, (rows, w), 0)
                   + r0) == idx
            pick = lambda c0: _lane_pick(
                lambda g: tab_ref[pl.ds(r0, rows), pl.ds(c0 + g, 1)], gmasks)
            f = jnp.where(hit, pick(col), 0.0)
            t = jnp.where(hit, pick(n_cols + col), 0.0)
            return (acc[0] + jnp.sum(f, axis=0, keepdims=True),
                    acc[1] + jnp.sum(t, axis=0, keepdims=True))
        return step

    def x_step(f):
        def step(r0, rows, acc):
            hit = (jax.lax.broadcasted_iota(jnp.int32, (rows, w), 0)
                   + r0) == f
            xs = x_ref[pl.ds(r0, rows), pl.ds(0, w)]
            return acc + jnp.sum(jnp.where(hit, xs, 0.0), axis=0,
                                 keepdims=True)
        return step

    if quant:
        ts = _lane_pick(lambda g: scale_ref[0, col + g], gmasks)
    idx = jnp.zeros((1, w), jnp.int32)
    for lvl in range(depth):            # static unroll: d select-compare levels
        lo = ((1 << lvl) - 1) // 8 * 8
        f, thr = _chunked(lo, (1 << (lvl + 1)) - 1, node_step(idx),
                          (zero, zero))
        if quant:                       # int8 codes -> fp32, ±127 = ±inf
            thr = jnp.where(thr == 127, jnp.inf,
                            jnp.where(thr == -127, -jnp.inf, thr * ts))
        xv = _chunked(0, n_features, x_step(f.astype(jnp.int32)), zero)
        idx = 2 * idx + 1 + (xv > thr).astype(jnp.int32)

    # leaf select: one-hot over (grove, leaf) lanes of this tree's row,
    # contracted on the MXU chunk by chunk
    key = idx - (n_leaves - 1)
    if gmasks:
        key = key + n_leaves * _lane_pick(lambda g: g, gmasks)

    def chunk(k, acc):
        c0 = pl.multiple_of(k * LEAF_ROWS, LEAF_ROWS)
        rows = leaf_ref[tree, :, pl.ds(c0, LEAF_ROWS)].astype(jnp.float32)
        if quant:                       # dequantize the rows, then select
            lane_g = (jax.lax.broadcasted_iota(jnp.int32, (1, LEAF_ROWS), 1)
                      + c0) >> depth
            rows = rows * _lane_pick(
                lambda g: scale_ref[1, col + g],
                [lane_g == g for g in range(len(gmasks))] if gmasks else None)
        hit = (jax.lax.broadcasted_iota(jnp.int32, (LEAF_ROWS, w), 0)
               + c0) == key
        return acc + jnp.dot(rows, hit.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)

    return jax.lax.fori_loop(0, leaf_ref.shape[2] // LEAF_ROWS, chunk,
                             jnp.zeros((C, w), jnp.float32))


def leaf_rows(leaf: jax.Array) -> jax.Array:
    """``[K, G, leaves, C]`` packed leaves -> ``[K, C, width]`` with lanes
    ``g * leaves + leaf``, zero-padded to whole leaf-select steps."""
    K, G, L, C = leaf.shape
    rows = jnp.transpose(leaf, (0, 3, 1, 2)).reshape(K, C, G * L)
    return jnp.pad(rows, ((0, 0), (0, 0),
                          (0, round_up(G * L, LEAF_ROWS) - G * L)))


def x_columns(x: jax.Array, n_lanes: int) -> jax.Array:
    """``[B, F]`` inputs -> ``[feature_rows(F), n_lanes]`` fp32, batch on
    lanes, zero-padded (padded features are never selected)."""
    B, F = x.shape
    return jnp.pad(x.astype(jnp.float32),
                   ((0, n_lanes - B), (0, feature_rows(F) - F))).T


# --------------------------------------------------------------------------
# per-grove kernel
# --------------------------------------------------------------------------

def _tree_traverse_kernel(tab_ref, leaf_ref, scale_ref, x_ref, out_ref, *,
                          depth: int, n_features: int, quant: bool):
    t = leaf_ref.shape[0]
    total = None
    for j in range(t):
        d = walk_tree(tab_ref, leaf_ref, scale_ref, x_ref, x_ref.shape[1],
                      None, tree=j, col=j, n_cols=t, depth=depth,
                      n_features=n_features, quant=quant)
        total = d if total is None else total + d
    out_ref[...] = total / t


def tree_traverse_vmem(*, n_trees: int, depth: int, n_classes: int,
                       n_features: int, itemsize: int, block_b: int) -> int:
    """Modeled VMEM bytes: resident tables + double-buffered x/out blocks +
    one walk's temporaries."""
    L = 1 << depth
    tables = (node_table_bytes(n_trees, L - 1)
              + leaf_table_bytes(n_trees, n_classes, L, itemsize))
    lane = (2 * 4 * (feature_rows(n_features) + round_up(n_classes, 8))
            + walk_lane_bytes(n_classes=n_classes))
    return tables + block_b * lane


def tree_traverse_pallas(feature: jax.Array, threshold: jax.Array,
                         leaf: jax.Array, x: jax.Array,
                         thr_scale: jax.Array | None = None,
                         leaf_scale: jax.Array | None = None,
                         *, block_b: int = 128,
                         interpret: bool | None = None) -> jax.Array:
    """[t,N] x [t,N] x [t,L,C] x [B,F] -> [B,C] grove probabilities.

    ``threshold``/``leaf`` may be fp32, bf16 or int8 (then ``thr_scale``
    [t,1] / ``leaf_scale`` [t,1,1] carry the per-tree dequant scales;
    omitted scales default to ones).  ``B`` need not divide ``block_b``:
    the batch is dead-padded with zero rows up to the next block boundary
    (the padded walks are discarded) and the output is sliced back to ``B``.
    """
    B, F = x.shape
    t, L, C = leaf.shape
    depth = int(np.log2(L) + 0.5)
    block_b = lane_block(block_b, B)
    interpret = resolve_interpret(interpret)
    if thr_scale is None:
        thr_scale = jnp.ones((t, 1), jnp.float32)
    if leaf_scale is None:
        leaf_scale = jnp.ones((t, 1, 1), jnp.float32)

    ws = tree_traverse_vmem(n_trees=t, depth=depth, n_classes=C,
                            n_features=F, itemsize=leaf.dtype.itemsize,
                            block_b=block_b)
    if ws >= VMEM_BUDGET:
        raise vmem_error(
            "grove", ws,
            f"{t} trees, depth {depth}, {C} classes, {threshold.dtype} "
            f"tables in their VMEM layout + block_b={block_b} batch state")

    Bp = round_up(B, block_b)
    scales = jnp.stack([thr_scale.reshape(t), leaf_scale.reshape(t)])
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)    # whole, single copy
    out = pl.pallas_call(
        functools.partial(_tree_traverse_kernel, depth=depth, n_features=F,
                          quant=threshold.dtype == jnp.int8),
        grid=(Bp // block_b,),
        in_specs=[vmem, vmem,
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((feature_rows(F), block_b),
                               lambda i: (0, i))],
        out_specs=pl.BlockSpec((C, block_b), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((C, Bp), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(node_columns(feature, threshold), leaf_rows(leaf[:, None]),
      scales.astype(jnp.float32), x_columns(x, Bp))
    return out[:, :B].T

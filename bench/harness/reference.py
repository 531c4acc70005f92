"""The plain reference: Algorithm 2 of the paper over the benchmark's own
field, in float64 numpy, and the comparison that decides ``correct``.

Independent of the program: tables are quantized here from the field the
benchmark made (never from the program's packs), trees are walked one
comparison at a time, and the start grove of every served request is the
documented draw of its replica, ``randint(fold_in(key(server_seed +
replica), dispatch_no), (span,), 0, G)``, recomputed here on the host CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

# A served answer that differs from the reference counts against the run
# unless it sits on the gate's knife edge: the program accumulates in
# float32 (rounding ~1e-7 on values <= 1 over at most 16 trees), the
# reference in float64, so a margin within EDGE_TOL of the threshold, or a
# top-2 tie within EDGE_TOL, may fall either way.
EDGE_TOL = 1e-5

# per-tree symmetric grids: (threshold qmax, leaf qmax); threshold codes
# +-(qmax+1) stand for the +-inf "always go left" padding
_GRID = {"int8": (126, 127), "int4": (6, 7)}


def _per_tree_scale(a: np.ndarray, axes: tuple, qmax: int) -> np.ndarray:
    finite = np.where(np.isfinite(a), np.abs(a), np.float32(0))
    amax = finite.max(axis=axes, keepdims=True) + np.float32(1e-12)
    return (amax / np.float32(qmax)).astype(np.float32)


def tables(threshold: np.ndarray, leaf: np.ndarray, precision: str):
    """(threshold, leaf) as float32 values after storage at ``precision``:
    ``fp32`` as made, ``bf16`` rounded to nearest even, ``int8`` / ``int4``
    symmetric per-tree grids (amax over the tree's finite entries / qmax,
    round half to even, dequantized as code x scale)."""
    thr = np.asarray(threshold, np.float32)
    lf = np.asarray(leaf, np.float32)
    if precision == "fp32":
        return thr, lf
    if precision == "bf16":
        return (thr.astype(ml_dtypes.bfloat16).astype(np.float32),
                lf.astype(ml_dtypes.bfloat16).astype(np.float32))
    if precision not in _GRID:
        raise ValueError(f"no reference storage for precision {precision!r}")
    tq, lq = _GRID[precision]
    ts = _per_tree_scale(thr, (thr.ndim - 1,), tq)
    code = np.clip(np.round(thr / ts), -tq, tq)
    dq = (code * ts).astype(np.float32)
    dq = np.where(thr == np.inf, np.float32(np.inf), dq)
    dq = np.where(thr == -np.inf, np.float32(-np.inf), dq)
    ls = _per_tree_scale(lf, (lf.ndim - 2, lf.ndim - 1), lq)
    lcode = np.clip(np.round(lf / ls), -lq, lq)
    return dq, (lcode * ls).astype(np.float32)


def evaluate(feature, threshold, leaf, x, start, n_groves: int):
    """Every hop of Algorithm 2 for each row, without stopping.

    feature/threshold [T, 2**d-1], leaf [T, 2**d, C] with trees ``[g*k,
    (g+1)*k)`` forming grove ``g``; x [n, F]; start [n].  Hop ``h`` visits
    grove ``(start + h) % G`` and adds the mean of its trees' leaf
    distributions.  Returns (labels [n, G], margins [n, G]) after hops
    1..G: the first argmax and the top-1 minus top-2 gap of the
    hop-normalized probabilities, in float64."""
    T, n_int = feature.shape
    depth = int(np.log2(n_int + 1) + 0.5)
    k = T // n_groves
    n = x.shape[0]
    rows = np.arange(n)
    acc = np.zeros((n, leaf.shape[-1]), np.float64)
    labels = np.empty((n, n_groves), np.int64)
    margins = np.empty((n, n_groves), np.float64)
    for h in range(n_groves):
        g = (np.asarray(start, np.int64) + h) % n_groves
        contrib = np.zeros_like(acc)
        for j in range(k):
            t = g * k + j
            idx = np.zeros((n,), np.int64)
            for _ in range(depth):
                go = x[rows, feature[t, idx]] > threshold[t, idx]
                idx = 2 * idx + 1 + go
            contrib += leaf[t, idx - n_int].astype(np.float64)
        acc += contrib / k
        norm = acc / (h + 1)
        top = np.sort(norm, axis=1)
        labels[:, h] = np.argmax(norm, axis=1)
        margins[:, h] = top[:, -1] - top[:, -2]
    return labels, margins


def gate(labels, margins, thresh):
    """(label, hops) of each row under the MaxDiff gate: stop after the
    first hop whose margin reaches ``thresh``, else after all G."""
    G = margins.shape[1]
    reached = margins >= np.asarray(thresh, np.float64)[..., None]
    hops = np.where(reached.any(1), reached.argmax(1) + 1, G)
    return labels[np.arange(len(hops)), hops - 1], hops


def off_edge(label_p, hops_p, labels_r, margins_r, thresh):
    """Per row: True where the served (label, hops) differs from the
    reference away from the knife edge.

    Hops differ: the hop where one side stopped and the other went on is
    ``h = min(hops)``; on the edge iff the reference margin there is within
    EDGE_TOL of the threshold.  Hops agree but labels differ: on the edge
    iff the reference top-2 gap at that hop is within EDGE_TOL.  A hop
    count outside 1..G is never on the edge."""
    label_p = np.asarray(label_p, np.int64)
    hops_p = np.asarray(hops_p, np.int64)
    G = margins_r.shape[1]
    label_r, hops_r = gate(labels_r, margins_r, thresh)
    rows = np.arange(len(label_p))
    valid = (hops_p >= 1) & (hops_p <= G)
    h = np.clip(np.minimum(hops_p, hops_r), 1, G) - 1
    m = margins_r[rows, h]
    thr = np.asarray(thresh, np.float64)
    hop_diff = hops_p != hops_r
    edge_h = np.abs(m - thr) <= EDGE_TOL
    lab_diff = ~hop_diff & (label_p != label_r)
    edge_l = m <= EDGE_TOL
    return ~valid | (hop_diff & ~edge_h) | (lab_diff & ~edge_l)


def host_device():
    """The host CPU device where its backend is loaded, else None (the
    default device)."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def start_draws(server_seed: int, replica: int, dispatch_nos, span: int,
                n_groves: int) -> np.ndarray:
    """[len(dispatch_nos), span] start groves of one replica's dispatches,
    on the host CPU."""
    with jax.default_device(host_device()):
        key = jax.random.key(server_seed + replica)
        steps = jnp.asarray(np.asarray(dispatch_nos, np.int32))
        draw = jax.jit(jax.vmap(lambda s: jax.random.randint(
            jax.random.fold_in(key, s), (span,), 0, n_groves)))
        return np.asarray(draw(steps))

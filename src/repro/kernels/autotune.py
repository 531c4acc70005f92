"""Measured autotuner for the fused FoG kernel and the trainer histogram.

``block_b`` (batch lanes per launch block) and ``compact`` (live-lane
compaction) are the two knobs that set the fused kernel's VMEM traffic and
its per-hop work, and their best values move with the pack: int8 tables
leave ~3x more VMEM for lane state than fp32, a wide field (many groves x
deep trees) squeezes the batch block down, and compaction only pays when
the workload's early-exit profile actually empties lanes.  Hand-picking
one constant (the historical ``block_b=128``/``256``) therefore leaves
latency on the table somewhere in the (precision, field size) plane.

This module keeps a best-config table keyed by the device and the pack
signature:

    key = (device_kind, precision, n_heads, n_groves, grove_size, depth,
           n_classes, n_features)

A winner is a measurement on one kind of device, so the device kind (as
JAX reports it, ``jax.devices()[0].device_kind``) leads every key: a block
size timed on a CPU host is never served on a TPU, nor one TPU generation's
on another.  Cache-file entries without a device kind are ignored.

``best_config(key)`` is what the engine consults when a policy leaves
``block_b`` unset: a measured entry wins; otherwise the ANALYTIC SEED —
derived from the ``fit_block_b`` VMEM model (whole 128-lane rows) — answers
immediately, so an untuned engine never stalls to benchmark.  ``tune()``
runs the measured sweep (halving ladder of aligned block sizes from the
VMEM fit, x compaction on/off, best-of-k timing on representative inputs)
and caches the winner; set ``FOG_AUTOTUNE_CACHE=/path/file.json`` to
persist winners across processes (loaded lazily, written atomically), the
re-tune story for new hardware.

The device forest trainer shares the table (and the cache file).  Its
level-wise histogram kernel has three tile knobs (``block_n`` batch lanes,
``block_r`` resident rows, ``block_f`` feature columns) plus a path
crossover ``matmul_max_r``: below that many (node, class) rows the Pallas
one-hot matmul kernel wins, above it the XLA scatter path does (deep
levels spread few samples over many nodes, where a dense one-hot wastes
its width).  Histogram entries are keyed by the device and the trainer
signature

    key = (device_kind, "hist", n_trees, depth, n_features, n_bins, n_classes)

``best_hist_config(...)`` mirrors ``best_config``: a measured/cached entry
wins, else an analytic seed (scatter-everywhere on interpreted backends,
matmul for the top levels on a compiled TPU); ``tune_histogram()`` measures
both paths per level size and the block_n ladder on synthetic shapes.
"""
from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.kernels.fused_fog import LANE_ALIGN, fit_block_b

CACHE_ENV = "FOG_AUTOTUNE_CACHE"

# analytic fallback cap: past ~256 lanes the walk's gather width saturates
# the VPU and bigger blocks only grow VMEM pressure
SEED_CAP = 256

# in-process best-config table: key tuple -> TuneResult
_CACHE: dict[tuple, "TuneResult"] = {}
_LOADED_FROM: str | None = None


@dataclass(frozen=True)
class TuneResult:
    """One winning fused-kernel configuration."""
    block_b: int
    compact: bool
    measured_s: float | None = None   # None: analytic seed, never measured
    source: str = "analytic"          # "analytic" | "measured" | "cache-file"

    def to_dict(self) -> dict:
        return {"block_b": self.block_b, "compact": self.compact,
                "measured_s": self.measured_s, "source": self.source}


@dataclass(frozen=True)
class HistConfig:
    """One winning trainer-histogram configuration (tile sizes + path
    crossover; see kernels/histogram.py)."""
    block_n: int
    block_r: int
    block_f: int
    matmul_max_r: int                 # Pallas one-hot path while R <= this
    measured_s: float | None = None   # None: analytic seed, never measured
    source: str = "analytic"          # "analytic" | "measured" | "cache-file"

    def to_dict(self) -> dict:
        return {"block_n": self.block_n, "block_r": self.block_r,
                "block_f": self.block_f, "matmul_max_r": self.matmul_max_r,
                "measured_s": self.measured_s, "source": self.source}


def device_kind() -> str:
    """The kind of device this process evaluates on (``device_kind``)."""
    return jax.devices()[0].device_kind


def pack_key(pack, n_features: int) -> tuple:
    """The (device, precision, field size) signature a tuned config is
    valid for."""
    return (device_kind(), pack.precision, pack.n_heads, pack.n_groves,
            pack.grove_size, pack.depth, pack.n_classes, int(n_features))


def hist_key(n_trees: int, depth: int, n_features: int, n_bins: int,
             n_classes: int) -> tuple:
    """The device + trainer signature a tuned histogram config is valid
    for."""
    return (device_kind(), "hist", int(n_trees), int(depth),
            int(n_features), int(n_bins), int(n_classes))


def _key_str(key: tuple) -> str:
    return "/".join(str(k) for k in key)


def _parse_key(kstr: str) -> tuple | None:
    """A cache-file key back to its tuple, or None for an entry that names
    no device kind (or is otherwise malformed): such entries are ignored."""
    from repro.forest.pack import PRECISIONS
    parts = kstr.split("/")
    n_ints = {"hist": 5, **{p: 6 for p in PRECISIONS}}
    if len(parts) < 2 or parts[1] not in n_ints \
            or len(parts) != 2 + n_ints[parts[1]]:
        return None
    try:
        return (parts[0], parts[1], *(int(p) for p in parts[2:]))
    except ValueError:
        return None


def analytic_block_b(pack, n_features: int) -> int:
    """Seed config from the VMEM model alone: the largest aligned block
    that fits beside the packed tables, capped at SEED_CAP (floor of
    LANE_ALIGN so a viable pack always gets a runnable block)."""
    tables = pack.layout("fused")
    fit = fit_block_b(*tables, n_features=n_features)
    return max(LANE_ALIGN, min(fit, SEED_CAP)) if fit > 0 else 0


def candidate_blocks(pack, n_features: int, batch_b: int | None = None) -> list[int]:
    """The measured sweep's block_b ladder: the VMEM fit (aligned), then
    halvings down to LANE_ALIGN — every size that changes the grid."""
    fit = fit_block_b(*pack.layout("fused"), n_features=n_features)
    if fit <= 0:
        return []
    top = min(fit, 1024)
    if batch_b is not None:
        top = min(top, batch_b + (-batch_b) % LANE_ALIGN)
    top -= top % LANE_ALIGN
    out = []
    b = max(top, LANE_ALIGN)
    while b >= LANE_ALIGN:
        out.append(b)
        b //= 2
        b -= b % LANE_ALIGN
    return out or [LANE_ALIGN]


def best_config(pack, n_features: int) -> TuneResult:
    """The config the engine uses when ``block_b`` is unset: the cached
    measured winner for this pack signature, else the analytic seed."""
    _load_cache_file()
    key = pack_key(pack, n_features)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    return TuneResult(block_b=analytic_block_b(pack, n_features),
                      compact=True, source="analytic")


def tune(pack, x, start, thresh, budget, *, max_hops: int,
         repeats: int = 3, persist: bool = True,
         blocks: list[int] | None = None) -> TuneResult:
    """Measured sweep over block_b candidates x compaction on/off.

    ``x/start/thresh/budget`` should be representative of serving traffic —
    the winner is workload-dependent (compaction pays exactly when this
    threshold profile exits lanes early).  Best-of-``repeats`` wall time
    per candidate, winner cached under the pack signature (and persisted
    to ``$FOG_AUTOTUNE_CACHE`` when set and ``persist``).  ``blocks``
    narrows the sweep to an explicit block_b ladder (VMEM-infeasible
    entries are dropped); default is the full halving ladder from the
    VMEM fit."""
    from repro.kernels import ops

    key = pack_key(pack, int(x.shape[1]))
    tables = pack.layout("fused")
    feasible = candidate_blocks(pack, int(x.shape[1]), int(x.shape[0]))
    if blocks is None:
        blocks = feasible
    else:
        cap = max(feasible) if feasible else 0
        blocks = [b for b in blocks if LANE_ALIGN <= b <= cap] or feasible
    if not blocks:
        raise ValueError(
            f"pack {key} has no VMEM-feasible block_b; shrink the field or "
            "use precision=\"int8\"")

    best: TuneResult | None = None
    for block_b in blocks:
        for compact in (False, True):
            def run():
                p, h = ops.fused_fog(*tables[:3], x, start, thresh, budget,
                                     *tables[3:], max_hops=max_hops,
                                     block_b=block_b, compact=compact)
                jax.block_until_ready((p, h))
            run()                                  # compile / warm
            t = min(_timed(run) for _ in range(repeats))
            if best is None or t < best.measured_s:
                best = TuneResult(block_b=block_b, compact=compact,
                                  measured_s=t, source="measured")
    _CACHE[key] = best
    if persist:
        _save_cache_file()
    return best


def analytic_hist_config(n_trees: int, depth: int, n_features: int,
                         n_bins: int, n_classes: int) -> HistConfig:
    """Seed histogram config, answered without benchmarking.

    Tile sizes come straight from the kernel's VMEM model; the path
    crossover depends on the backend: a compiled TPU keeps the one-hot
    matmul (MXU work against a VMEM-resident block) while the row count is
    modest, whereas an interpreted backend pays the matmul's full
    ``N*R*F*bins`` flop bill on the host VPU-less path, where the XLA
    scatter always wins — so the interpreted seed is scatter-everywhere.
    """
    from repro.kernels import histogram
    from repro.kernels.tree_traverse import resolve_interpret
    block_f = histogram.default_block_f(n_features, n_bins)
    matmul_max_r = 0 if resolve_interpret(None) else 2048
    return HistConfig(block_n=histogram.BLOCK_N, block_r=histogram.BLOCK_R,
                      block_f=block_f, matmul_max_r=matmul_max_r,
                      source="analytic")


def best_hist_config(n_trees: int, depth: int, n_features: int, n_bins: int,
                     n_classes: int) -> HistConfig:
    """The config the device trainer uses: the cached measured winner for
    this trainer signature, else the analytic seed."""
    _load_cache_file()
    key = hist_key(n_trees, depth, n_features, n_bins, n_classes)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    return analytic_hist_config(n_trees, depth, n_features, n_bins,
                                n_classes)


# skip timing the one-hot matmul path once its modeled flops pass this
# (interpreted hosts would stall for minutes measuring a foregone loss)
_HIST_TUNE_FLOP_CAP = 2e9


def tune_histogram(n_trees: int, depth: int, n_features: int, n_bins: int,
                   n_classes: int, *, n_samples: int, seed: int = 0,
                   repeats: int = 3, persist: bool = True,
                   blocks: tuple[int, ...] = (512, 1024, 2048)) -> HistConfig:
    """Measured histogram sweep on synthetic level shapes.

    Times the Pallas one-hot kernel over the ``blocks`` batch-tile ladder
    at a shallow probe level, then walks the levels deepest-rows-first
    timing Pallas vs scatter per row count; ``matmul_max_r`` is the
    largest row count where the kernel still wins (it loses monotonically
    as rows grow, so the walk stops at the first loss).  On an interpreted
    backend the Pallas side is never timed (interpret-mode matmuls lose by
    construction and cost minutes to prove it); only the segment-sum
    levels are measured, with ``matmul_max_r = 0``.  Winner cached under
    the trainer signature (persisted to ``$FOG_AUTOTUNE_CACHE`` when set
    and ``persist``).
    """
    from repro.kernels import histogram
    from repro.kernels.tree_traverse import resolve_interpret

    key = hist_key(n_trees, depth, n_features, n_bins, n_classes)
    seed_cfg = analytic_hist_config(n_trees, depth, n_features, n_bins,
                                    n_classes)
    interp = resolve_interpret(None)
    k = jax.random.key(seed)
    ky, kb, kw = jax.random.split(k, 3)
    y = jax.random.randint(ky, (n_samples,), 0, n_classes)
    bins = jax.random.randint(kb, (n_samples, n_features), 0, n_bins)
    w = jnp.ones((n_trees, n_samples), jnp.float32)

    def node_at(level: int):
        return jax.random.randint(kw, (n_trees, n_samples), 0, 1 << level)

    def timed(fn) -> float:
        out = fn()
        jax.block_until_ready(out)      # compile / warm
        return min(_timed(lambda: jax.block_until_ready(fn()))
                   for _ in range(repeats))

    # block_n ladder at a shallow probe level (cheap enough to matmul)
    best_bn, best_t = seed_cfg.block_n, None
    if not interp:
        probe = min(2, depth - 1)
        node = node_at(probe)
        for bn in blocks:
            t = timed(lambda: histogram.histogram_level_pallas(
                node, y, w, bins, n_nodes=1 << probe, n_bins=n_bins,
                n_classes=n_classes, block_n=bn, block_r=seed_cfg.block_r,
                block_f=seed_cfg.block_f))
            if best_t is None or t < best_t:
                best_bn, best_t = bn, t

    # per-level crossover: largest R where the Pallas path still wins.
    # The win region must stay contiguous from R=0 (the dispatcher tests
    # R <= matmul_max_r), so growth stops at the first level Pallas loses.
    matmul_max_r, total = 0, 0.0
    pallas_alive = not interp
    for level in range(depth):
        r = (1 << level) * n_classes
        flops = n_samples * r * n_features * n_bins
        node = node_at(level)
        kw_args = dict(n_nodes=1 << level, n_bins=n_bins,
                       n_classes=n_classes)
        t_sc = timed(lambda: histogram.histogram_level_scatter(
            node, y, w, bins, **kw_args))
        if not pallas_alive or flops > _HIST_TUNE_FLOP_CAP:
            total += t_sc
            continue
        t_pl = timed(lambda: histogram.histogram_level_pallas(
            node, y, w, bins, block_n=best_bn, block_r=seed_cfg.block_r,
            block_f=seed_cfg.block_f, **kw_args))
        total += min(t_pl, t_sc)
        if t_pl < t_sc:
            matmul_max_r = r
        else:
            pallas_alive = False

    best = HistConfig(block_n=best_bn, block_r=seed_cfg.block_r,
                      block_f=seed_cfg.block_f, matmul_max_r=matmul_max_r,
                      measured_s=total, source="measured")
    _CACHE[key] = best
    if persist:
        _save_cache_file()
    return best


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def clear_cache() -> None:
    """Drop every in-process entry (tests; does not touch the cache file)."""
    global _LOADED_FROM
    _CACHE.clear()
    _LOADED_FROM = None


def _load_cache_file() -> None:
    global _LOADED_FROM
    path = os.environ.get(CACHE_ENV)
    if not path or _LOADED_FROM == path:
        return
    _LOADED_FROM = path
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return
    for kstr, cfg in raw.items():
        key = _parse_key(kstr)
        if key is None or key in _CACHE:  # no device kind / fresher in-process
            continue
        if key[1] == "hist":
            _CACHE[key] = HistConfig(block_n=int(cfg["block_n"]),
                                     block_r=int(cfg["block_r"]),
                                     block_f=int(cfg["block_f"]),
                                     matmul_max_r=int(cfg["matmul_max_r"]),
                                     measured_s=cfg.get("measured_s"),
                                     source="cache-file")
        else:
            _CACHE[key] = TuneResult(block_b=int(cfg["block_b"]),
                                     compact=bool(cfg["compact"]),
                                     measured_s=cfg.get("measured_s"),
                                     source="cache-file")


def _save_cache_file() -> None:
    path = os.environ.get(CACHE_ENV)
    if not path:
        return
    payload = {_key_str(k): v.to_dict() for k, v in _CACHE.items()}
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)

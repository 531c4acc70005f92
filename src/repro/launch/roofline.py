"""FoG roofline model: dtype-aware bytes-moved per backend vs machine peaks.

The paper's energy claim is a traffic claim — FoG wins because the grove
walk stays on-chip — so every latency we publish should come with "how far
from the bandwidth bound is that?".  This module answers it analytically,
per backend, from quantities the engine already knows (pack shape, packed
table bytes, hop statistics), instead of parsing compiled HLO: the FoG
kernels' traffic is *designed*, not emergent, so the model is a short
closed form per backend.

Traffic model (per evaluation of a ``[B, F]`` batch):

* **per-hop backends** ("reference", "pallas"): every loop iteration
  re-materializes each lane's grove slice from the packed tables
  (``table_bytes / n_groves`` per lane — dtype-aware: an int8 pack moves a
  quarter of fp32) plus the lane's fp32 row, probability state update and
  loop bookkeeping.  Iterations = ``max_hops`` for the fixed-trip scan,
  the observed max hop count for the lazy while_loop.

* **fused**: the tables are pinned ONCE per launch (× chunks when the
  engine auto-chunks) and per-lane state crosses HBM once — in: row +
  start/thresh/budget/live; out: proba + hops.  Hop count doesn't multiply
  HBM traffic at all; that is the whole point of the kernel.

* **ring**: fused-style per-shard pinning plus the rotation's collective
  bytes (probability state crossing ICI ``iters`` times).

FLOPs: a lane-hop walks one grove per head — ``O·t`` trees × (2 ops per
level × depth + C leaf accumulates) — plus the MaxDiff gate (~``8·O·C``).
Compute lane-hops are ``Σ hops`` when the backend skips exited lanes
(fused-compacted) and ``B × iters`` when it computes dead lanes anyway.

``bound`` is whichever of ``bytes/peak_bw`` and ``flops/peak_flops`` is
slower; ``achieved`` = ideal over measured.  Machine peaks come from a
:class:`MachineSpec`: pass one, or the device kind JAX reports
(``jax.devices()[0].device_kind``), which is looked up in ``PEAKS`` — the
one table of published peaks, each with its source.  A kind that is not in
the table is an error: a device that cannot be priced gets no roofline
share, never a borrowed one.

The LM dry-run HLO cost model that used to live here is first-class in
:mod:`repro.launch.hlo_cost`; importing its names from here still works
behind a ``DeprecationWarning`` (see ``__getattr__`` at the bottom).
"""
from __future__ import annotations

import dataclasses
import warnings


@dataclasses.dataclass(frozen=True)
class MachineSpec:
    """Peak rates the roofline is drawn against."""
    name: str
    peak_flops: float    # FLOP/s
    peak_bw: float       # HBM (main-memory) bytes/s
    ici_bw: float = 0.0  # interconnect bytes/s (ring backend); 0 = ignore


# TPU v5e, one chip — Google Cloud documentation, "TPU v5e": 197 TFLOP/s
# bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect
TPU_V5E = MachineSpec("tpu-v5e", peak_flops=197e12, peak_bw=819e9,
                      ici_bw=1600e9 / 8)

# published peaks by the device kind JAX reports
PEAKS = {"TPU v5 lite": TPU_V5E}


def spec_for(device_kind: str) -> MachineSpec:
    """The published peaks of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); a roofline share needs the device's "
            "peaks") from None

# fixed per-lane bookkeeping bytes a per-hop iteration touches: live mask,
# hop counter read+write, threshold and budget reads
_LANE_LOOP_BYTES = 20
# per-lane one-time fused traffic besides the fp32 row and outputs:
# start + thresh + budget (4 B each) + int8 live mask
_LANE_FUSED_IN_BYTES = 13


@dataclasses.dataclass(frozen=True)
class RooflineEstimate:
    """One backend's modeled traffic/compute and the resulting bound."""
    backend: str
    spec: MachineSpec | None
    bytes_moved: float
    flops: float

    @property
    def memory_s(self) -> float:
        return self.bytes_moved / self.spec.peak_bw

    @property
    def compute_s(self) -> float:
        return self.flops / self.spec.peak_flops

    @property
    def bound(self) -> str:
        return "memory" if self.memory_s >= self.compute_s else "compute"

    @property
    def ideal_s(self) -> float:
        """No-overlap roofline time: the slower of the two terms."""
        return max(self.memory_s, self.compute_s)

    def achieved(self, measured_s: float) -> float:
        """Fraction of the roofline the measurement reaches (0 when the
        measurement is missing/zero — never a division error)."""
        if not measured_s or measured_s <= 0 or self.ideal_s <= 0:
            return 0.0
        return self.ideal_s / measured_s

    def to_dict(self, measured_s: float | None = None) -> dict:
        d = {"backend": self.backend, "bytes_moved": self.bytes_moved,
             "flops": self.flops}
        if self.spec is None:           # unpriced device: traffic only
            return d
        d.update(spec=self.spec.name, memory_s=self.memory_s,
                 compute_s=self.compute_s, bound=self.bound,
                 ideal_s=self.ideal_s)
        if measured_s is not None:
            d["achieved_pct"] = round(100.0 * self.achieved(measured_s), 4)
        return d


class RooflineModel:
    """Analytic FoG roofline for one packed field of groves.

    pack:       a :class:`~repro.forest.pack.ForestPack` — supplies the
                head/grove/tree/class shape and the dtype-aware table bytes
    n_features: width of the input rows
    spec:       a :class:`MachineSpec`, or a device kind looked up in
                ``PEAKS`` (unknown kinds raise ``ValueError``), or None
                for traffic and FLOPs only (no bound, no achieved share)
    """

    def __init__(self, pack, n_features: int,
                 spec: MachineSpec | str | None):
        self.pack = pack
        self.n_features = int(n_features)
        self.spec = spec_for(spec) if isinstance(spec, str) else spec

    # -- per-unit terms ---------------------------------------------------
    @property
    def lane_hop_flops(self) -> float:
        """Walk one grove per head for one lane: O·t trees × (compare +
        index update per level + C leaf accumulates), plus the MaxDiff
        gate over the [O, C] state."""
        p = self.pack
        walk = p.n_heads * p.grove_size * (2 * p.depth + p.n_classes)
        gate = 8 * p.n_heads * p.n_classes
        return float(walk + gate)

    @property
    def lane_hop_bytes(self) -> float:
        """Per-hop-backend traffic for one lane in one iteration: its
        grove's slice of the packed tables (dtype-aware), the fp32 row,
        the [O, C] fp32 probability state read+written, bookkeeping."""
        p = self.pack
        return (p.table_bytes / p.n_groves
                + 4 * self.n_features
                + 8 * p.n_heads * p.n_classes
                + _LANE_LOOP_BYTES)

    @property
    def lane_io_bytes(self) -> float:
        """Fused per-lane one-time HBM traffic: fp32 row + scalar knobs in,
        fp32 [O, C] proba + int32 hops out."""
        p = self.pack
        return (4 * self.n_features + _LANE_FUSED_IN_BYTES
                + 4 * p.n_heads * p.n_classes + 4)

    # -- per-backend estimates -------------------------------------------
    def estimate(self, backend: str, batch: int, *, iters: float,
                 hops_total: float | None = None, chunks: int = 1,
                 compact: bool = False) -> RooflineEstimate:
        """Model one evaluation.

        iters:      loop trip count the backend executed — ``max_hops``
                    for the fixed-trip scan backends, the observed max hop
                    count for early-exit loops (lazy reference, fused)
        hops_total: Σ per-lane hops (``batch × mean_hops``); defaults to
                    ``batch × iters`` (no early exit)
        chunks:     fused launches per evaluation (engine auto-chunking
                    re-pins the tables per chunk)
        compact:    fused live-lane compaction — compute scales with
                    Σ hops instead of batch × iters

        ``backend`` may be any engine row name (``"fused-compact"``,
        ``"pallas-chunked"``, ...); the traffic class is derived from its
        root (``fused*`` pins tables once per launch, ``ring*`` adds the
        ICI hop state, everything else — reference / reference-lazy /
        pallas — streams tables per hop) while the estimate reports the
        full name, so benchmark rows keep their own labels.
        """
        p = self.pack
        B = float(batch)
        if hops_total is None:
            hops_total = B * iters
        root = backend.split("-")[0]
        if root == "fused":
            byts = chunks * p.table_bytes + B * self.lane_io_bytes
            lane_hops = hops_total if compact else B * iters
            flops = lane_hops * self.lane_hop_flops
        elif root == "ring":
            # per-shard pin + the probability state crossing ICI every hop
            byts = chunks * p.table_bytes + B * self.lane_io_bytes
            flops = B * iters * self.lane_hop_flops
        else:  # per-hop backends: reference / reference-lazy / pallas
            byts = B * iters * self.lane_hop_bytes
            flops = B * iters * self.lane_hop_flops
        return RooflineEstimate(backend=backend, spec=self.spec,
                                bytes_moved=float(byts), flops=float(flops))


# --------------------------------------------------------------------------
# deprecation shim: the LM dry-run HLO cost model moved to launch/hlo_cost
# --------------------------------------------------------------------------

_MOVED = ("PEAK_FLOPS", "HBM_BW", "ICI_BW", "ICI_LINKS", "HloCostModel",
          "RooflineTerms", "analytic_model_flops", "extract",
          "collective_bytes_from_hlo", "_shape_bytes", "_SHAPE_RE",
          "_DTYPE_BYTES", "_COLLECTIVE_RE")


def __getattr__(name: str):
    if name in _MOVED:
        warnings.warn(
            f"repro.launch.roofline.{name} moved to repro.launch.hlo_cost; "
            "repro.launch.roofline is now the FoG-specific RooflineModel",
            DeprecationWarning, stacklevel=2)
        from repro.launch import hlo_cost
        return getattr(hlo_cost, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

"""FogPolicy — the one runtime-knob contract for Algorithm-2 evaluation.

The paper's value proposition is that threshold and hop count are *run-time*
knobs trading accuracy for energy (Fig. 5).  Every such knob lives here, in
one frozen, pytree-registered dataclass, instead of being scattered across
``FogEngine.__init__`` kwargs, positional ``eval`` arguments, and private
conventions in ``budget.py`` / ``serve/scheduler.py`` / ``models/fog_exit.py``:

===============  ============================================================
knob             meaning
===============  ============================================================
``threshold``    MaxDiff confidence gate — a scalar for the whole batch, or a
                 per-lane ``[B]`` vector (mixed-QoS batches: each lane buys
                 its own accuracy/energy point)
``max_hops``     global hop cap (static loop trip count); None = n_groves
``hop_budget``   per-lane energy cap — scalar or ``[B]`` int; a lane stops
                 hopping once it has consumed its budget even if still
                 unconfident (anytime inference under an energy contract)
``backend``      "reference" | "pallas" | "fused" | "ring"; None = engine
                 default
``block_b``      pallas batch tile; None = engine default
``chunk_b``      batch chunking (VMEM bound): an int, ``"auto"`` (chunk only
                 when the packed tables + batch footprint exceed the VMEM
                 budget, sized from the pack's per-chunk footprint), or
                 None = engine default
``lazy``         early-exit while_loop vs fixed-trip scan; None = engine
                 default
``precision``    packed-table dtype: "fp32" | "bf16" | "int8" (int8 reads a
                 quarter of the table bytes per hop and fits ~4x the field
                 in VMEM); None = engine default
``compact``      fused backend: permute live lanes to a contiguous prefix
                 each hop and walk only the covering power-of-two prefix
                 (bit-identical; pays when the threshold profile exits lanes
                 early); None = engine default
``interpret``    Pallas execution mode: None derives from the runtime
                 (compiled Mosaic on a real TPU, interpreted jnp elsewhere);
                 an explicit bool overrides — debugging a Mosaic miscompile
                 with True on TPU, or asserting compiled execution
===============  ============================================================

``threshold`` and ``hop_budget`` are pytree *data* (they may be traced,
per-lane arrays); everything else is static metadata, so a ``FogPolicy``
passes through ``jax.jit`` boundaries without retriggering compilation when
only the traced knobs change.

A policy is engine-agnostic: the same object drives ``FogEngine.eval``,
``FogClassifier.predict``, the ``budget.py`` design sweeps, the
continuous-batching scheduler (which assembles per-lane vectors from
per-request policies — see :func:`assemble`), and the LM early-exit gate in
``models/fog_exit.py``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.forest.pack import PRECISIONS  # noqa: E402  (re-export: the
# precision knob's domain lives with the packed-table layer)

BACKENDS = ("reference", "pallas", "fused", "ring")

# per-lane "no budget" sentinel: hops < NO_BUDGET is always true for any
# reachable hop count, so unbudgeted lanes are capped by max_hops alone
NO_BUDGET = 2**31 - 1


@partial(jax.tree_util.register_dataclass,
         data_fields=("threshold", "hop_budget"),
         meta_fields=("max_hops", "backend", "block_b", "chunk_b", "lazy",
                      "precision", "compact", "interpret"))
@dataclasses.dataclass(frozen=True)
class FogPolicy:
    """Every runtime knob of one Algorithm-2 evaluation, in one object."""

    threshold: float | jax.Array = 0.3
    max_hops: int | None = None
    hop_budget: int | jax.Array | None = None
    backend: str | None = None
    block_b: int | None = None
    chunk_b: int | str | None = None
    lazy: bool | None = None
    precision: str | None = None
    compact: bool | None = None
    interpret: bool | None = None

    def __post_init__(self):
        if self.backend is not None and self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"pick from {BACKENDS} (or None)")
        if self.precision is not None and self.precision not in PRECISIONS:
            raise ValueError(f"unknown precision {self.precision!r}; "
                             f"pick from {PRECISIONS} (or None)")
        if self.max_hops is not None and self.max_hops < 1:
            raise ValueError(f"max_hops must be >= 1, got {self.max_hops}")
        if isinstance(self.chunk_b, str):
            if self.chunk_b != "auto":
                raise ValueError(f"chunk_b must be an int, 'auto' or None, "
                                 f"got {self.chunk_b!r}")
        elif self.chunk_b is not None and self.chunk_b < 1:
            raise ValueError(f"chunk_b must be >= 1, got {self.chunk_b}")
        # a lane always spends its first hop before any gate can fire, so a
        # budget below 1 is unsatisfiable; validate when concrete (traced
        # budgets inside jit are the caller's contract)
        if (self.hop_budget is not None
                and not isinstance(self.hop_budget, jax.core.Tracer)):
            if (np.asarray(self.hop_budget) < 1).any():
                raise ValueError(
                    f"hop_budget must be >= 1 everywhere (the first hop is "
                    f"always spent), got {self.hop_budget}")

    # -- convenience -----------------------------------------------------
    def replace(self, **kw) -> "FogPolicy":
        """A copy with some knobs changed (frozen dataclass idiom)."""
        return dataclasses.replace(self, **kw)

    @property
    def per_lane(self) -> bool:
        """True when threshold or hop_budget carries a per-lane vector."""
        return (getattr(self.threshold, "ndim", 0) > 0
                or getattr(self.hop_budget, "ndim", 0) > 0)

    @property
    def static_overrides(self) -> tuple[str, ...]:
        """Names of the static knobs this policy sets (non-None).  Static
        knobs select compiled programs, so contexts that share one program
        across many policies (the serving scheduler) must reject them on
        per-request policies — except ``precision``, which the scheduler
        handles by dispatching one program per precision group."""
        return tuple(k for k in ("max_hops", "backend", "block_b",
                                 "chunk_b", "lazy", "precision", "compact",
                                 "interpret")
                     if getattr(self, k) is not None)

    # -- persistence -----------------------------------------------------
    def to_dict(self) -> dict:
        """JSON-safe scalar-knob dict (artifact persistence: FogClassifier
        saves, frontier dumps).  Per-lane policies are batch-shaped state
        and refuse to serialize."""
        if self.per_lane:
            raise ValueError(
                "cannot serialize a per-lane policy (its threshold/"
                "hop_budget vectors are batch-shaped)")

        def scalar(v):
            return v if v is None else np.asarray(v).item()

        return {"threshold": scalar(self.threshold),
                "max_hops": self.max_hops,
                "hop_budget": scalar(self.hop_budget),
                "backend": self.backend, "block_b": self.block_b,
                "chunk_b": self.chunk_b, "lazy": self.lazy,
                "precision": self.precision, "compact": self.compact,
                "interpret": self.interpret}

    @classmethod
    def from_dict(cls, d: dict) -> "FogPolicy":
        return cls(**d)

    # -- lane-vector materialization (the engines' single entry) ---------
    def lane_thresholds(self, B: int) -> jax.Array:
        """``threshold`` as a per-lane float32 ``[B]`` vector."""
        t = jnp.asarray(self.threshold, jnp.float32)
        if t.ndim == 0:
            return jnp.broadcast_to(t, (B,))
        if t.shape != (B,):
            raise ValueError(
                f"per-lane threshold has shape {t.shape}, batch is {B}")
        return t

    def lane_budgets(self, B: int) -> jax.Array:
        """``hop_budget`` as a per-lane int32 ``[B]`` vector (NO_BUDGET
        sentinel where unset — the max_hops loop bound still applies)."""
        if self.hop_budget is None:
            return jnp.full((B,), NO_BUDGET, jnp.int32)
        b = jnp.asarray(self.hop_budget, jnp.int32)
        if b.ndim == 0:
            return jnp.broadcast_to(b, (B,))
        if b.shape != (B,):
            raise ValueError(
                f"per-lane hop_budget has shape {b.shape}, batch is {B}")
        return b


# -- device-resident per-lane policy state (the packed serving path) -------
#
# The continuous batcher's packed fast path keeps each span's per-lane
# threshold / hop-budget vectors with the replica, written at admit /
# retire instead of re-assembled from the requests every step.  Lanes
# without an explicit per-request policy carry sentinels — NaN threshold /
# negative budget — that the jitted dispatch resolves against the step's
# default rung (``jnp.where``), so a governor rung change rewrites no lane.
# Retired lanes are stamped DEAD: threshold -1 confirms on the first hop
# (MaxDiff >= 0 > -1 always) and budget 1 hard-caps it, so empty lanes cost
# one hop and compact away instead of walking the default policy.

THRESH_DEFAULT = float("nan")
BUDGET_DEFAULT = -1
DEAD_THRESH = -1.0
DEAD_BUDGET = 1


def lane_knobs(policy: "FogPolicy | None") -> tuple[float, int]:
    """One lane's resident (threshold, hop_budget) encoding: concrete
    values for an explicit policy (an unset hop_budget is NO_BUDGET — the
    per-request contract fully overrides the default, matching
    :func:`assemble`), default sentinels otherwise."""
    if policy is None:
        return THRESH_DEFAULT, BUDGET_DEFAULT
    # float()/int() accept python numbers, np scalars and 0-d arrays
    # directly; wrapping in np.asarray costs ~2us per lane in the refill
    bud = (int(policy.hop_budget)
           if policy.hop_budget is not None else NO_BUDGET)
    return float(policy.threshold), bud


class LanePolicies:
    """One span's per-lane policy vectors, with dirty-lane tracking for a
    device splice of the lanes staged since the last one
    (:func:`~repro.core.engine.splice_slot_state`).  No serving replica
    uses it: the forest replica stages its knobs beside its rows and
    uploads the whole span.  All lanes start DEAD (the span serves nothing
    until admits arrive)."""

    def __init__(self, n: int):
        self.n = int(n)
        self.thresh = np.full((n,), DEAD_THRESH, np.float32)
        self.budget = np.full((n,), DEAD_BUDGET, np.int32)
        self._dirty = np.zeros((n,), bool)

    def stamp(self, lane: int, thr: float, bud: int) -> None:
        """Raw per-lane write (admit resolved knobs, flush re-stamps)."""
        self.thresh[lane] = thr
        self.budget[lane] = bud
        self._dirty[lane] = True

    def stamp_many(self, lanes, thr, bud) -> None:
        """Vectorized :meth:`stamp` — the hot-loop refill stages one bulk
        write per step instead of a Python call per lane."""
        self.thresh[lanes] = thr
        self.budget[lanes] = bud
        self._dirty[lanes] = True

    def admit(self, lane: int, policy: "FogPolicy | None" = None) -> None:
        self.stamp(lane, *lane_knobs(policy))

    def retire(self, lane: int) -> None:
        self.stamp(lane, DEAD_THRESH, DEAD_BUDGET)

    def retire_many(self, lanes) -> None:
        self.stamp_many(lanes, DEAD_THRESH, DEAD_BUDGET)

    @property
    def dirty(self) -> bool:
        return bool(self._dirty.any())

    def take_dirty(self):
        """``(idx, thresh, budget)`` of every lane staged since the last
        take (idx ascending for deterministic splices), clearing the
        mask."""
        idx = np.flatnonzero(self._dirty).astype(np.int32)
        self._dirty[idx] = False
        return idx, self.thresh[idx], self.budget[idx]

    def resolve(self, default: "FogPolicy") -> tuple[np.ndarray, np.ndarray]:
        """The full effective vectors under ``default`` — the host-side
        reference of what the jitted ``jnp.where`` resolution computes
        (tests + the synchronous conformance path)."""
        thr = np.where(np.isnan(self.thresh),
                       np.float32(np.asarray(default.threshold)),
                       self.thresh).astype(np.float32)
        def_bud = (int(np.asarray(default.hop_budget))
                   if default.hop_budget is not None else NO_BUDGET)
        bud = np.where(self.budget < 0, np.int32(def_bud),
                       self.budget).astype(np.int32)
        return thr, bud


def margin_backend(backend: "str | None") -> str:
    """Map an engine backend to the confidence-margin implementation the LM
    early-exit gate runs: kernel-flavored backends ("pallas", "fused") route
    the pallas top-2 kernel, everything else (incl. "ring", which has no
    meaning for the layer-grove gate) the jnp reference."""
    return "pallas" if backend in ("pallas", "fused") else "reference"


def assemble(policies: Sequence["FogPolicy | None"],
             default: "FogPolicy | None" = None) -> FogPolicy:
    """Stack per-request scalar policies into one per-lane batch policy.

    The continuous-batching scheduler holds one (possibly absent) scalar
    policy per slot; this builds the single ``FogPolicy`` whose ``threshold``
    / ``hop_budget`` are ``[n_slots]`` vectors, so one compiled decode step
    serves mixed-QoS traffic.  Static knobs (backend, block_b, ...) come
    from ``default`` — they select compiled programs and cannot vary by lane.
    """
    default = default if default is not None else FogPolicy()
    thr = [float(p.threshold if p is not None else default.threshold)
           for p in policies]
    budgets = [(p.hop_budget if p is not None else default.hop_budget)
               for p in policies]
    budget_vec = None
    if any(b is not None for b in budgets):
        budget_vec = np.asarray(
            [int(b) if b is not None else NO_BUDGET for b in budgets],
            np.int32)
    # host numpy on purpose: the vectors are assembled (and re-sliced by the
    # data-parallel dispatcher) every decode step — jnp arrays here would
    # cost a device round-trip per step before the jit boundary converts
    # them anyway
    return default.replace(threshold=np.asarray(thr, np.float32),
                           hop_budget=budget_vec)

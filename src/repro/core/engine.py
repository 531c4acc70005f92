"""FogEngine — the single owner of Algorithm 2 with pluggable backends.

The paper's hop-until-confident loop used to live in four divergent copies
(``fog_eval``, ``fog_eval_multioutput``, ``fog_eval_lazy`` and the ring in
``fog_ring.py``).  This module collapses them into one state machine whose
*per-hop update* — masked accumulate, hop count, normalize, MaxDiff gate —
is a pluggable backend:

==============  =============================================================
backend         per-hop update implementation
==============  =============================================================
``reference``   pure jnp (``kernels.ref.grove_aggregate_ref``), the oracle
``pallas``      fused VMEM hop-update kernel (``kernels.ops.grove_aggregate``);
                interpreted on CPU, Mosaic-compiled on TPU — one launch
                per hop
``fused``       the ENTIRE Algorithm-2 loop in one Pallas launch
                (``kernels.ops.fused_fog``): every grove table VMEM-pinned,
                the early-exit loop runs inside the kernel — the TPU
                analogue of the paper's PE
``ring``        ``shard_map`` + ``ppermute`` mesh ring (``fog_ring``) — the
                grove tables are partitioned over devices and queue entries
                rotate one ICI hop per round
==============  =============================================================

Every runtime knob — threshold (scalar or per-lane ``[B]``), hop caps and
per-lane hop budgets, backend selection, tiling, table precision — is owned
by a :class:`repro.core.policy.FogPolicy`; the canonical evaluation call is

    engine.eval(x, key, policy=FogPolicy(threshold=0.3))

(the old positional ``eval(x, key, thresh, max_hops)`` survives as a
deprecated shim).  Every backend runs the *identical* update math, so labels
and — critically — per-example hop counts (the paper's energy quantity) are
bit-identical across backends for the same starting groves, including under
per-lane thresholds and budgets.  ``sample_starts`` is the one place start
groves are drawn: on a single shard it reproduces the legacy ``fog_eval``
draw exactly; on an n-shard ring it stratifies starts so each shard begins
with an equal slice of the queue.

Grove tables are owned by a :class:`TableCache`: one packed
:class:`~repro.forest.pack.ForestPack` per precision ("fp32" | "bf16" |
"int8"), with the derived layouts (ring strided reorder, fused head-stack)
cached inside each pack.  Every backend evaluates the pack — the fused
kernel pins the packed bytes whole in VMEM, the per-hop backends gather and
dequantize per-lane slices — so switching ``FogPolicy(precision=...)``
swaps table dtypes without rebuilding the engine.  (The former
``engine.ring_tables`` / ``engine.fused_tables`` attributes are gone; use
``engine.tables.get(layout, precision)``.)

Batches larger than VMEM are evaluated in fixed-size chunks (``chunk_b``)
with one compiled program reused across chunks; per-lane policy vectors are
dead-padded alongside the inputs.  ``chunk_b="auto"`` (the fused backend's
default) only chunks when the packed tables + whole-batch footprint exceed
the VMEM budget, sizing chunks from the pack's per-chunk footprint.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.confidence import maxdiff
from repro.core.energy import EnergyModel, EnergyReport
from repro.core.grove import GroveCollection
from repro.core.policy import BACKENDS, PRECISIONS, FogPolicy
from repro.forest.pack import ForestPack
from repro.kernels import ops, ref

# batch tile when nothing chooses one: per-hop backends always use it;
# the fused backend only falls back here when the autotuner has no
# feasible block (tables alone over the VMEM budget — the kernel's
# ValueError then explains the remedies)
DEFAULT_BLOCK_B = 256


@partial(jax.tree_util.register_dataclass,
         data_fields=("proba", "label", "hops"), meta_fields=())
@dataclasses.dataclass(frozen=True)
class FogResult:
    """The one result contract every backend returns.

    proba: [B, C] (or [B, O, C] multi-output) final normalized probabilities
    label: [B]    (or [B, O]) argmax labels
    hops:  [B]    groves that processed each example, 1-based — the energy
                  quantity (the paper's `hops` counts forwards = groves-1)
    """
    proba: jax.Array
    label: jax.Array
    hops: jax.Array


@partial(jax.tree_util.register_dataclass,
         data_fields=("proba", "label", "hops", "energy_pj"),
         meta_fields=("model",))
@dataclasses.dataclass(frozen=True)
class EvalReport(FogResult):
    """What ``FogEngine.eval`` returns: the FogResult contract plus the
    energy telemetry every consumer used to re-derive by hand
    (``HopMeter`` + ``fog_energy``).

    energy_pj: [B] estimated pJ per example — ``model.lane_pj(hops)``,
               computed on device alongside the evaluation outputs
    model:     the :class:`~repro.core.energy.EnergyModel` the estimate was
               priced with (topology + the precision the evaluation actually
               ran at) — callers can re-price or invert budgets without
               reaching back into the engine
    """
    energy_pj: jax.Array = None
    model: EnergyModel = None

    @property
    def precision(self) -> str:
        return self.model.precision

    @property
    def mean_energy_pj(self) -> float:
        return float(np.asarray(self.energy_pj).mean())

    @property
    def mean_energy_nj(self) -> float:
        return self.mean_energy_pj * 1e-3

    def energy_report(self) -> EnergyReport:
        """Float64 post-hoc report over this evaluation's hops —
        bit-identical to the legacy ``fog_energy(hops, ...)`` call."""
        return self.model.report(np.asarray(self.hops))


def sample_starts(key: jax.Array, B: int, G: int,
                  n_shards: int = 1) -> jax.Array:
    """Random start grove per example (Algorithm 2 line 3).

    ``n_shards == 1`` reproduces the legacy ``fog_eval`` draw bit-exactly.
    For an n-shard ring the draw is stratified — exactly B/n lanes start in
    each shard residue class (start % n == shard) so the queue slices are
    equal-sized — while staying uniform over all G groves marginally.
    """
    if n_shards == 1:
        return jax.random.randint(key, (B,), 0, G)
    if B % n_shards or G % n_shards:
        raise ValueError(
            f"batch B={B} and n_groves G={G} must both divide over "
            f"{n_shards} ring shards")
    kp, ko = jax.random.split(key)
    shard = jax.random.permutation(
        kp, jnp.tile(jnp.arange(n_shards), B // n_shards))
    offset = jax.random.randint(ko, (B,), 0, G // n_shards)
    return shard + n_shards * offset


def hop_update(prob, contrib, live, hops, thresh, *, backend: str = "reference",
               block_b: int = 256):
    """One Algorithm-2 hop update (lines 7-11), dispatched by backend.

    ``thresh`` is a scalar or per-lane ``[B]`` vector.  Returns
    (prob, hops, live, margin).  This is the single shared update both
    FogEngine loops and the distributed ring build on.
    """
    _check_step_backend(backend)
    if backend == "pallas":
        return ops.grove_aggregate(prob, contrib, live, hops, thresh,
                                   block_b=block_b)
    return ref.grove_aggregate_ref(prob, contrib, live, hops, thresh)


def confidence_margin(probs: jax.Array, *, backend: str = "reference",
                      block_b: int = 256) -> jax.Array:
    """MaxDiff margin [..., C] -> [...]; pallas routes the top-2 kernel."""
    _check_step_backend(backend)
    if backend == "pallas" and probs.ndim == 2:
        return ops.top2_confidence(probs, block_b=min(block_b, probs.shape[0]))
    return maxdiff(probs)


def _check_step_backend(backend: str) -> None:
    # the per-step primitives have no ring variant (the ring composes them)
    if backend not in ("reference", "pallas"):
        raise ValueError(f"unknown step backend {backend!r}; "
                         "pick 'reference' or 'pallas'")


# --------------------------------------------------------------------------
# jitted evaluation cores (reference / pallas).  Multi-output heads are
# flattened to [B*O, C] so the same fused update serves both; the min-over-
# outputs confidence rule (paper footnote 1) is applied on the margins.
# --------------------------------------------------------------------------

def _contrib(pack: ForestPack, g_idx, x):
    """Per-hop grove contribution from packed tables, flattened over output
    heads: [B*O, C].  Gathers stay at the pack's dtype; the gathered slices
    dequantize to fp32 before the walk (bit-identical to the legacy
    GroveCollection path for an fp32 pack)."""
    if pack.n_heads == 1:
        return pack.predict_proba(0, g_idx, x)
    rows = [pack.predict_proba(o, g_idx, x) for o in range(pack.n_heads)]
    return jnp.stack(rows, axis=1).reshape(-1, pack.n_classes)


def _repeat_lanes(v, n_out):
    """[B] lane state -> [B*O] (each head shares its lane's liveness)."""
    return v if n_out == 1 else jnp.repeat(v, n_out)


def _step(pack, x, start, thresh, budget, j, prob, live, hops, backend,
          block_b):
    """Shared hop body: returns updated (prob, live, hops) for [B*O, C].

    ``thresh`` is per-lane [B] float32; ``budget`` per-lane [B] int32 — a
    lane that has consumed its hop budget dies even while unconfident.
    """
    O = pack.n_heads
    G = pack.n_groves
    g_idx = (start + j) % G
    contrib = _contrib(pack, g_idx, x)
    prob, hops_f, live_f, margin = hop_update(
        prob, contrib, _repeat_lanes(live, O), _repeat_lanes(hops, O),
        _repeat_lanes(thresh, O), backend=backend, block_b=block_b)
    if O == 1:
        return prob, live_f & (hops_f < budget), hops_f
    # min-over-outputs rule: a lane stays live until EVERY head is confident
    margin = margin.reshape(-1, O).min(axis=1)
    hops = hops_f.reshape(-1, O)[:, 0]
    live = live & (margin < thresh) & (hops < budget)
    return prob, live, hops


@partial(jax.jit, static_argnames=("max_hops", "backend", "block_b", "lazy",
                                   "compact", "interpret"))
def _eval_core(pack: ForestPack, x, start, thresh, budget, max_hops: int,
               backend: str, block_b: int, lazy: bool,
               compact: bool = True, interpret: bool | None = None):
    B = x.shape[0]
    O = pack.n_heads
    C = pack.n_classes
    if block_b is None:  # external positional callers (serving plane)
        block_b = DEFAULT_BLOCK_B
    thresh = jnp.broadcast_to(jnp.asarray(thresh, jnp.float32), (B,))
    budget = jnp.broadcast_to(jnp.asarray(budget, jnp.int32), (B,))

    if backend == "fused":
        # the whole early-exit state machine runs inside ONE kernel launch;
        # `lazy` is moot (the in-kernel while_loop always exits early).
        # The pack's canonical storage IS the head-stacked [O, G, ...]
        # layout, pinned in VMEM at its packed width, so one launch serves
        # the min-over-outputs rule and every precision alike.
        feat, thr_tab, leaf, ts, ls = pack.layout("fused")
        proba, hops = ops.fused_fog(
            feat, thr_tab, leaf,
            x, start, thresh, budget, ts, ls,
            max_hops=max_hops, block_b=block_b, compact=compact,
            interpret=interpret)
        if O == 1:
            proba = proba[:, 0]
        return FogResult(proba=proba,
                         label=jnp.argmax(proba, axis=-1).astype(jnp.int32),
                         hops=hops)
    prob0 = jnp.zeros((B * O, C), jnp.float32)
    live0 = jnp.ones((B,), bool)
    hops0 = jnp.zeros((B,), jnp.int32)

    if lazy:
        def cond(state):
            j, _, live, _ = state
            return (j < max_hops) & live.any()

        def body(state):
            j, prob, live, hops = state
            prob, live, hops = _step(pack, x, start, thresh, budget, j, prob,
                                     live, hops, backend, block_b)
            return (j + 1, prob, live, hops)

        _, prob, _, hops = jax.lax.while_loop(
            cond, body, (jnp.zeros((), jnp.int32), prob0, live0, hops0))
    else:
        def body(carry, j):
            prob, live, hops = carry
            prob, live, hops = _step(pack, x, start, thresh, budget, j, prob,
                                     live, hops, backend, block_b)
            return (prob, live, hops), None

        (prob, _, hops), _ = jax.lax.scan(
            body, (prob0, live0, hops0), jnp.arange(max_hops))

    denom = jnp.maximum(_repeat_lanes(hops, O), 1)[:, None]
    prob_norm = prob / denom
    if O > 1:
        prob_norm = prob_norm.reshape(B, O, C)
    return FogResult(proba=prob_norm,
                     label=jnp.argmax(prob_norm, axis=-1).astype(jnp.int32),
                     hops=hops)


# --------------------------------------------------------------------------
# device-resident lane state: row splices by lane index (no serving
# replica calls these; the forest replica uploads its whole span)
# --------------------------------------------------------------------------

@partial(jax.jit, donate_argnums=(0,))
def _splice(buf, idx, vals):
    # mode="drop": padding indices point one past the end and fall away,
    # so every splice width compiles once per power-of-two pad size
    return buf.at[idx].set(vals, mode="drop")


@jax.jit
def _splice_copy(buf, idx, vals):
    return buf.at[idx].set(vals, mode="drop")


def splice_lanes(buf: jax.Array, idx, vals, *,
                 donate: bool = True) -> jax.Array:
    """In-place row update of a device-resident lane buffer.

    With ``donate=True`` (the default) ``buf`` is DONATED: the caller must
    replace its reference with the return value
    (``buf = splice_lanes(buf, idx, vals)``).  Pass ``donate=False`` when
    an in-flight async computation may still be READING ``buf`` — donating
    a buffer with live readers stalls the enqueue until they drain, which
    serializes a double-buffered dispatch pipeline; the copying splice
    keeps the enqueue non-blocking and costs one buffer copy (trivial at
    per-span row-buffer sizes).

    ``idx`` / the leading axis of ``vals`` are padded to the next power of
    two (capped at the buffer length) with out-of-range indices that
    ``mode="drop"`` discards, so admit/retire bursts of any size reuse a
    handful of compiled splice programs instead of one per burst width.
    """
    idx = np.asarray(idx, np.int32).reshape(-1)
    n = int(buf.shape[0])
    vals = np.asarray(vals)
    if idx.size == 0:
        return buf
    width = min(n, 1 << max(0, int(idx.size - 1).bit_length()))
    pad = width - idx.size
    if pad > 0:
        idx = np.concatenate([idx, np.full((pad,), n, np.int32)])
        vals = np.concatenate(
            [vals, np.zeros((pad,) + vals.shape[1:], vals.dtype)])
    elif pad < 0:
        raise ValueError(
            f"splice of {idx.size} lanes into a {n}-lane buffer")
    fn = _splice if donate else _splice_copy
    return fn(buf, idx, vals.astype(buf.dtype, copy=False))


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _splice3(x, thr, bud, idx, rows, t, b):
    return (x.at[idx].set(rows, mode="drop"),
            thr.at[idx].set(t, mode="drop"),
            bud.at[idx].set(b, mode="drop"))


@jax.jit
def _splice3_copy(x, thr, bud, idx, rows, t, b):
    return (x.at[idx].set(rows, mode="drop"),
            thr.at[idx].set(t, mode="drop"),
            bud.at[idx].set(b, mode="drop"))


def splice_slot_state(x: jax.Array, thr: jax.Array, bud: jax.Array,
                      idx, rows, t, b, *,
                      donate: bool = True):
    """Fused :func:`splice_lanes` over a replica's THREE slot buffers
    (feature rows, thresholds, hop budgets) sharing ONE lane index set —
    a refill burst costs a single jitted launch instead of three.  Same
    power-of-two padding / ``mode="drop"`` program reuse and the same
    donation contract: with ``donate=True`` all three buffers are donated
    and must be rebound to the returned triple; ``donate=False`` copies,
    for callers whose previous dispatch may still be reading them."""
    idx = np.asarray(idx, np.int32).reshape(-1)
    if idx.size == 0:
        return x, thr, bud
    n = int(x.shape[0])
    rows = np.asarray(rows)
    t = np.asarray(t)
    b = np.asarray(b)
    width = min(n, 1 << max(0, int(idx.size - 1).bit_length()))
    pad = width - idx.size
    if pad > 0:
        idx = np.concatenate([idx, np.full((pad,), n, np.int32)])
        rows = np.concatenate(
            [rows, np.zeros((pad,) + rows.shape[1:], rows.dtype)])
        t = np.concatenate([t, np.zeros((pad,), t.dtype)])
        b = np.concatenate([b, np.zeros((pad,), b.dtype)])
    elif pad < 0:
        raise ValueError(
            f"splice of {idx.size} lanes into a {n}-lane buffer")
    fn = _splice3 if donate else _splice3_copy
    return fn(x, thr, bud, idx,
              rows.astype(x.dtype, copy=False),
              t.astype(thr.dtype, copy=False),
              b.astype(bud.dtype, copy=False))


# --------------------------------------------------------------------------
# packed-table ownership
# --------------------------------------------------------------------------

class TableCache:
    """One :class:`ForestPack` per precision, derived layouts cached inside.

    Replaces the engine's former ad-hoc ``_ring_tables`` / ``_fused_tables``
    pair: every evaluation path asks this cache for its (layout, dtype)
    view, so a given precision's tables are packed once per engine and the
    ring reorder / head-stack are computed once per pack.
    """

    def __init__(self, gcs_fn):
        # a zero-arg callable, not the groves themselves: an engine seeded
        # with a loaded pack serves it without ever materializing fp32
        # tables — groves are only realized if ANOTHER precision is asked
        self._gcs_fn = gcs_fn
        self._packs: dict[str, ForestPack] = {}

    def seed(self, pack: ForestPack) -> None:
        """Adopt an existing pack (e.g. a loaded model artifact) as the
        cached entry for its precision."""
        self._packs[pack.precision] = pack

    def pack(self, precision: str) -> ForestPack:
        """The canonical packed tables at ``precision`` (built on first use)."""
        if precision not in self._packs:
            gcs = self._gcs_fn()
            gc = gcs if len(gcs) > 1 else gcs[0]
            self._packs[precision] = ForestPack.from_groves(gc, precision)
        return self._packs[precision]

    def get(self, layout: str, precision: str, n_shards: int = 1):
        """Table tuple for one (layout, dtype) pair — see
        :meth:`ForestPack.layout`."""
        return self.pack(precision).layout(layout, n_shards)


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

class FogEngine:
    """Owns the Algorithm-2 state machine; backends plug in the hop update.

    gc:        GroveCollection, a tuple of them (multi-output heads with
               identical (n_groves, grove_size)), or a
               :class:`~repro.forest.pack.ForestPack` (e.g. a loaded model
               artifact — the pack is adopted into the table cache and its
               precision becomes the engine default).
    policy:    default :class:`FogPolicy` applied when ``eval`` is called
               without one.  A per-call policy REPLACES it — the traced
               knobs (threshold, hop_budget) come wholly from the policy
               you pass; only its None-valued static knobs (max_hops,
               backend, block_b, chunk_b, lazy, precision) fall back to the
               engine defaults.
    precision: default packed-table dtype ("fp32" | "bf16" | "int8") for
               policies that leave ``precision`` None; defaults to "fp32"
               (or the adopted pack's precision).
    mesh/axis: required for the ring backend; n_groves % mesh.shape[axis]
               must be 0 (each shard hosts a strided subset of groves).
    use_kernels: ring only — run the Pallas tree-traversal PE per shard.

    ``backend`` / ``block_b`` / ``chunk_b`` / ``lazy`` / ``compact`` /
    ``interpret`` kwargs remain as engine-level defaults for any policy
    that leaves them None; packed tables live in ``self.tables`` (a
    :class:`TableCache`).  ``block_b=None`` (the default) lets the fused
    backend consult the :mod:`~repro.kernels.autotune` best-config table
    per (precision, field size) — a measured winner when one is cached,
    the analytic VMEM-model seed otherwise — while per-hop backends use
    ``DEFAULT_BLOCK_B``.
    """

    def __init__(self, gc, *, backend: str = "reference",
                 block_b: int | None = None,
                 chunk_b: int | str | None = None,
                 mesh=None, axis: str = "grove", use_kernels: bool = False,
                 lazy: bool = False, policy: FogPolicy | None = None,
                 precision: str | None = None,
                 compact: bool | None = None,
                 interpret: bool | None = None):
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; pick from {BACKENDS}")
        self._seed_pack = gc if isinstance(gc, ForestPack) else None
        if self._seed_pack is not None:
            # groves realize lazily (to_groves dequantizes to fp32): an
            # int8 artifact serves from its packed bytes alone
            self._gcs = None
        else:
            self._gcs = (tuple(gc) if isinstance(gc, (tuple, list))
                         else (gc,))
            g0 = self._gcs[0]
            for g in self._gcs[1:]:
                if (g.n_groves, g.grove_size) != (g0.n_groves,
                                                  g0.grove_size):
                    raise ValueError(
                        "multi-output heads need identical (n_groves, "
                        f"grove_size); got {(g.n_groves, g.grove_size)} vs "
                        f"{(g0.n_groves, g0.grove_size)}")
        if precision is None:
            precision = (self._seed_pack.precision
                         if self._seed_pack is not None else "fp32")
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}; "
                             f"pick from {PRECISIONS}")
        self.backend = backend
        self.block_b = block_b
        self.chunk_b = chunk_b
        self.precision = precision
        self.compact = compact
        self.interpret = interpret
        self.mesh = mesh
        self.axis = axis
        self.use_kernels = use_kernels
        self.lazy = lazy
        self.policy = policy if policy is not None else FogPolicy()
        self.tables = TableCache(lambda: self.gcs)
        self._energy_models: dict[tuple[str, int], EnergyModel] = {}
        self._n_features: int | None = None
        if self._seed_pack is not None:
            self.tables.seed(self._seed_pack)
        if use_kernels and backend != "ring":
            raise ValueError("use_kernels applies to the ring backend only "
                             "(the pallas backend always runs the fused "
                             "hop-update kernel)")
        if backend == "ring":
            self._check_ring_config(lazy=lazy, chunk_b=chunk_b)

    def _check_ring_config(self, *, lazy: bool, chunk_b: int | None) -> None:
        if self.mesh is None:
            raise ValueError("ring backend needs a mesh")
        if self.multi_output:
            raise NotImplementedError("ring backend is single-output")
        if lazy or chunk_b is not None:
            raise ValueError("lazy/chunk_b are not supported on the "
                             "ring backend (the ring always runs the "
                             "fixed max_hops rotation schedule)")
        n_shards = self.mesh.shape[self.axis]
        if self.n_groves % n_shards:
            raise ValueError(
                f"n_groves={self.n_groves} not divisible by "
                f"{n_shards} ring shards")
        if self.use_kernels and self.n_groves != n_shards:
            raise ValueError(
                "use_kernels needs one grove per shard (the multi-"
                "grove gather path has no Pallas tree-traversal PE)")

    # -- properties ------------------------------------------------------
    @property
    def gcs(self) -> tuple[GroveCollection, ...]:
        """Per-head GroveCollections; for a pack-seeded engine these are
        dequantized fp32 views, realized only on first access."""
        if self._gcs is None:
            self._gcs = self._seed_pack.to_groves()
        return self._gcs

    @property
    def n_groves(self) -> int:
        if self._seed_pack is not None:
            return self._seed_pack.n_groves
        return self._gcs[0].n_groves

    @property
    def n_shards(self) -> int:
        if self.backend == "ring" and self.mesh is not None:
            return self.mesh.shape[self.axis]
        return 1

    @property
    def multi_output(self) -> bool:
        if self._seed_pack is not None:
            return self._seed_pack.n_heads > 1
        return len(self._gcs) > 1

    # -- policy resolution ----------------------------------------------
    def resolve(self, policy: FogPolicy | None = None) -> FogPolicy:
        """Fill a policy's None knobs from the engine defaults.

        ``block_b``/``compact`` may legitimately remain None after this:
        the fused evaluation path then consults the autotuner's best-config
        table for the resolved (pack, n_features) — see
        :mod:`repro.kernels.autotune` — and the per-hop backends fall back
        to ``DEFAULT_BLOCK_B``.
        """
        p = policy if policy is not None else self.policy
        return p.replace(
            max_hops=p.max_hops if p.max_hops is not None else self.n_groves,
            backend=p.backend if p.backend is not None else self.backend,
            block_b=p.block_b if p.block_b is not None else self.block_b,
            chunk_b=p.chunk_b if p.chunk_b is not None else self.chunk_b,
            lazy=p.lazy if p.lazy is not None else self.lazy,
            precision=(p.precision if p.precision is not None
                       else self.precision),
            compact=p.compact if p.compact is not None else self.compact,
            interpret=(p.interpret if p.interpret is not None
                       else self.interpret))

    # -- evaluation ------------------------------------------------------
    def eval(self, x: jax.Array, key: jax.Array, thresh=None,
             max_hops: int | None = None, *,
             policy: FogPolicy | None = None) -> FogResult:
        """GCEval(X, policy) — Algorithm 2, any backend.

        Canonical call: ``eval(x, key, policy=FogPolicy(...))``.  The
        positional ``(thresh, max_hops)`` form is deprecated.
        """
        if isinstance(thresh, FogPolicy):
            # a policy passed positionally (the decode_step_fog calling
            # convention) is the canonical form, not the deprecated one
            if policy is not None or max_hops is not None:
                raise TypeError("pass a single FogPolicy (positionally or "
                                "via policy=), without extra thresh/"
                                "max_hops arguments")
            policy, thresh = thresh, None
        if policy is not None and (thresh is not None or max_hops is not None):
            raise TypeError("pass either policy= or the deprecated "
                            "(thresh, max_hops) arguments, not both")
        if policy is None and (thresh is not None or max_hops is not None):
            warnings.warn(
                "FogEngine.eval(x, key, thresh, max_hops) is deprecated; "
                "pass eval(x, key, policy=FogPolicy(threshold=..., "
                "max_hops=...)) instead",
                DeprecationWarning, stacklevel=2)
            policy = self.policy.replace(
                threshold=thresh if thresh is not None else
                self.policy.threshold,
                max_hops=max_hops)
        p = self.resolve(policy)
        backend, max_hops = p.backend, p.max_hops
        if backend == "ring":
            self._check_ring_config(lazy=bool(p.lazy), chunk_b=p.chunk_b)
        x = jnp.asarray(x)
        B = x.shape[0]
        thresh_v = p.lane_thresholds(B)
        budget_v = p.lane_budgets(B)
        n_shards = self.mesh.shape[self.axis] if backend == "ring" else 1
        start = sample_starts(key, B, self.n_groves, n_shards)
        if backend == "ring":
            res = self._eval_ring(x, start, thresh_v, budget_v, max_hops,
                                  p.precision)
        else:
            res = self._eval_chunked(x, start, thresh_v, budget_v, max_hops,
                                     backend, p.block_b, p.chunk_b, p.lazy,
                                     p.precision, p.compact, p.interpret)
        # every evaluation path carries its own energy telemetry: callers
        # read res.energy_pj instead of re-deriving HopMeter + fog_energy
        self._n_features = int(x.shape[1])
        model = self.energy_model(p.precision, x.shape[1])
        return EvalReport(proba=res.proba, label=res.label, hops=res.hops,
                          energy_pj=model.lane_pj(res.hops), model=model)

    __call__ = eval

    def energy_model(self, precision: str | None = None,
                     n_features: int | None = None) -> EnergyModel:
        """The engine's :class:`EnergyModel` at ``precision`` (default: the
        engine default precision).  ``n_features`` defaults to the pack's
        feature-index domain only implicitly via the last evaluation; pass
        it explicitly when pricing before any eval."""
        precision = precision if precision is not None else self.precision
        if n_features is None:
            n_features = self._n_features
            if n_features is None:
                raise ValueError(
                    "n_features unknown before the first eval; pass "
                    "energy_model(precision, n_features=...) explicitly")
        key = (precision, int(n_features))
        model = self._energy_models.get(key)
        if model is None:
            model = EnergyModel.from_pack(
                self.tables.pack(precision), n_features)
            self._energy_models[key] = model
        return model

    def _resolve_chunk(self, backend, pack: ForestPack, B: int, block_b: int,
                       chunk_b, n_features: int):
        """Concrete chunk size, or None for whole-batch evaluation.

        An explicit int is respected as-is.  ``"auto"`` / None on the fused
        backend chunk ONLY when the packed tables plus the whole batch's
        VMEM footprint exceed the budget, and then size the chunk from the
        pack's per-chunk footprint (largest lane count that fits beside the
        resident tables) — an int8 pack that fits where fp32 would not
        therefore runs un-chunked.  On the per-hop backends (no resident
        tables) ``"auto"`` never chunks.
        """
        if isinstance(chunk_b, int):
            return chunk_b if B > chunk_b else None
        if backend != "fused":
            return None
        from repro.kernels.fused_fog import fit_block_b, vmem_working_set
        from repro.kernels.tree_traverse import VMEM_BUDGET, lane_block
        tables = pack.layout("fused")
        ws = vmem_working_set(*tables, block_b=lane_block(block_b, B),
                              n_features=n_features)
        if ws < VMEM_BUDGET:
            return None
        fit = fit_block_b(*tables, n_features=n_features)
        if fit < 1:
            return None   # tables alone over budget: let the kernel's
            # ValueError explain (chunking cannot shrink resident tables)
        # ws over budget forces fit < min(block_b, B): each chunk is one
        # (shrunken) kernel block via the min(block_b, cb) at the call site
        return min(fit, B)

    def _eval_chunked(self, x, start, thresh, budget, max_hops, backend,
                      block_b, chunk_b, lazy, precision, compact=None,
                      interpret=None) -> FogResult:
        B = x.shape[0]
        pack = self.tables.pack(precision)
        if block_b is None or (compact is None and backend == "fused"):
            # unset knobs resolve from the autotuner: the cached measured
            # winner for this (precision, field size), else the analytic
            # VMEM-model seed; per-hop backends just take the default tile
            if backend == "fused":
                from repro.kernels import autotune
                cfg = autotune.best_config(pack, int(x.shape[1]))
                if block_b is None:
                    block_b = cfg.block_b or DEFAULT_BLOCK_B
                if compact is None:
                    compact = cfg.compact
            elif block_b is None:
                block_b = DEFAULT_BLOCK_B
        compact = True if compact is None else compact
        cb = self._resolve_chunk(backend, pack, B, block_b, chunk_b,
                                 x.shape[1])
        if cb is None:
            return _eval_core(pack, x, start, thresh, budget, max_hops,
                              backend, min(block_b, B), lazy, compact,
                              interpret)
        pad = (-B) % cb
        if pad:  # dead-pad the tail chunk so every chunk hits one compile;
            # padded lanes are discarded, so they get thresh=-1 / budget=1 —
            # any margin clears a negative gate, so they die on hop 1 and
            # never keep an early-exit while_loop (lazy or in-kernel fused)
            # spinning after the real lanes have exited
            x = jnp.concatenate([x, jnp.repeat(x[:1], pad, axis=0)])
            start = jnp.concatenate([start, jnp.zeros((pad,), start.dtype)])
            thresh = jnp.concatenate(
                [thresh, jnp.full((pad,), -1.0, thresh.dtype)])
            budget = jnp.concatenate(
                [budget, jnp.ones((pad,), budget.dtype)])
        chunks = [
            _eval_core(pack, x[i:i + cb], start[i:i + cb],
                       thresh[i:i + cb], budget[i:i + cb], max_hops,
                       backend, min(block_b, cb), lazy, compact, interpret)
            for i in range(0, B + pad, cb)
        ]
        out = jax.tree.map(lambda *ls: jnp.concatenate(ls)[:B], *chunks)
        return out

    def _eval_ring(self, x, start, thresh, budget, max_hops,
                   precision) -> FogResult:
        from repro.core.fog_ring import ring_eval
        tables = self.tables.get("ring", precision,
                                 self.mesh.shape[self.axis])
        proba, hops = ring_eval(
            self.gcs[0], x, start, thresh, max_hops, self.mesh, self.axis,
            use_kernels=self.use_kernels, tables=tables,
            hop_budget=budget)
        return FogResult(proba=proba,
                         label=jnp.argmax(proba, axis=-1).astype(jnp.int32),
                         hops=hops)


# --------------------------------------------------------------------------
# hop accounting shared with the serving path
# --------------------------------------------------------------------------

class HopMeter:
    """DEPRECATED streaming hop counter.

    Evaluation results now carry their own telemetry: ``FogEngine.eval``
    returns an :class:`EvalReport` with per-lane ``energy_pj`` and the
    pricing :class:`EnergyModel`, and the serving scheduler accumulates
    :class:`~repro.serve.scheduler.ServeStats` (fed to an
    ``EnergyGovernor`` when one is installed).  This shim keeps the old
    accounting arithmetic working for external callers.
    """

    def __init__(self) -> None:
        warnings.warn(
            "HopMeter is deprecated; read EvalReport.energy_pj from "
            "FogEngine.eval (or ContinuousBatcher.stats on the serving "
            "path) instead",
            DeprecationWarning, stacklevel=2)
        self.total_hops = 0
        self.n_events = 0

    def update(self, hops) -> None:
        h = np.asarray(hops)
        self.total_hops += int(h.sum())
        self.n_events += int(h.size)

    def reset(self) -> None:
        """Clear the accounting (e.g. between scheduler runs)."""
        self.total_hops = 0
        self.n_events = 0

    @property
    def mean_hops(self) -> float:
        return self.total_hops / max(1, self.n_events)

    def summary(self, n_groves: int) -> str:
        return (f"hops/event {self.mean_hops:.2f} "
                f"(grove fraction {self.mean_hops / max(1, n_groves):.2f}, "
                f"{self.n_events} events)")

"""Batched request scheduler for serving (continuous batching + FoG).

A slot-based continuous-batching scheduler: a fixed batch of ``n_slots``
lanes; finished/empty lanes are refilled from the request queue each step
(the standard vLLM-style slot model, minus paged KV).  Per-step grove
usage (hops) is accumulated per request, giving the per-request
energy/FLOP accounting that mirrors the paper's per-input hop counter.

One serving step.  The batcher drives a
:class:`~repro.serve.dispatch.DeviceDispatcher` whose replicas keep each
lane's state (feature row or token, cache length, knobs): a refill stages
the admitted lanes' prompts and knobs on their replica, each step
dispatches once per serving bucket, and a harvest folds the returned
``(next, hops, energy)`` into the requests.  The forest's replicas
(:meth:`~repro.serve.dispatch.ForestReplicaServer.packed_factory`) run the
whole step in one fused launch per bucket; a host
``decode_fn(tokens, lengths, policy)`` passed in place of a dispatcher
runs behind the same protocol on a one-replica dispatcher
(:class:`~repro.serve.dispatch.HostReplica`).

Mixed-QoS serving: every :class:`Request` may carry its own
:class:`~repro.core.policy.FogPolicy` (threshold / hop budget), stamped
on its lane at admission; lanes without one take the step's default
policy — one continuous batch, one compiled program, every lane buying its
own accuracy/energy point.

Mixed-precision serving: a request's policy may additionally set
``precision`` ("fp32" | "bf16" | "int8" packed tables).  Precision selects
a compiled program, so it cannot ride the per-lane vectors; instead the
scheduler buckets lanes by (model, version, precision) and dispatches once
per bucket present, each lane harvested from its own bucket's call.  A
homogeneous batch — the common case — costs exactly one dispatch.

Data-parallel serving: with several replicas each bucket's dispatch fans
out across the devices its lanes touch.  The refill routes each burst of
admitted requests to ONE replica, the one with the most free slots (ties
in turn after the replica used last), and spills into the next in turn
only when it is full: a step's dispatch then touches one replica and the
replicas take turns (``_route``).

Admission control: ``max_queue`` bounds the request queue.  When it is
full, ``shed_policy`` decides who pays: ``"reject"`` sheds the incoming
request (``submit`` returns False), ``"oldest"`` evicts the oldest queued
request to admit the new one.  Shed requests are marked ``req.shed``,
collected in ``batcher.shed_requests``, and counted in
``ServeStats.n_shed`` / ``shed_rate`` — overload becomes a measured,
bounded signal instead of an unbounded latency tail.

Energy governance: install an :class:`~repro.serve.governor.EnergyGovernor`
and the batcher serves under an nJ/classification SLO — each step's default
policy is the governor's active ladder rung, every step's hop telemetry
feeds its rolling estimate, and the governor steps down the ladder (tighten
threshold -> int8 -> cut hop budget) on a breach, back up when headroom
returns.  A request may carry ``energy_budget_nj`` instead of an explicit
policy: the governor resolves it against the calibrated frontier into the
highest-accuracy rung fitting that budget (hop budget clamped so the
contract is hard).  Telemetry lives in :class:`ServeStats`.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from collections import deque
from typing import Callable

import jax
import numpy as np

from repro.core.policy import NO_BUDGET, FogPolicy, lane_knobs
from repro.serve.dispatch import (NO_SPAN, PHASES, DeviceDispatcher,
                                  host_replica_factory, span)
from repro.serve.governor import lane_pricer


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # [P] int32
    max_new_tokens: int = 32
    # per-request QoS contract (scalar threshold / hop budget); None = the
    # batcher's default policy
    policy: FogPolicy | None = None
    # per-request energy contract: resolved at submit() into a policy via
    # the batcher's governor (mutually exclusive with an explicit policy)
    energy_budget_nj: float | None = None
    # registry tenant this request evaluates against (None = the batcher's
    # single built-in model, the pre-registry behavior)
    model: str | None = None
    # QoS tier label for per-tier shed/done/energy telemetry (ServeStats
    # breaks out counters per distinct label)
    tier: str = "default"
    # the registry version serving this request: resolved ONCE at slot
    # assignment (registry.route) and pinned, so a hot-swap mid-decode
    # never migrates an in-flight request between versions.  Pre-set it to
    # bypass routing.
    version: int | None = None
    # filled by the scheduler:
    generated: list = dataclasses.field(default_factory=list)
    hops: list = dataclasses.field(default_factory=list)
    done: bool = False
    # set by admission control when the request is dropped under overload
    shed: bool = False
    # wall-clock stamps for latency accounting: submit() stamps t_submit
    # (shed requests included — the shed tail is part of the latency
    # story), slot assignment t_admit (one clock read per refill burst),
    # completion t_done.  Callers may pre-stamp t_submit.
    t_submit: float | None = None
    t_admit: float | None = None
    t_done: float | None = None

    @property
    def tenant(self) -> str | None:
        """Alias of ``model`` (the registry/ledger vocabulary)."""
        return self.model


@dataclasses.dataclass
class ServeStats:
    """Fleet-level serving telemetry: hop counts of every decoded event, plus modeled pJ when a governor (or
    its energy model) is installed to price them."""

    total_hops: int = 0
    n_events: int = 0
    total_pj: float = 0.0
    has_energy: bool = False
    # events that actually carried a pJ price — the mean_energy_nj
    # denominator.  Mixing priced and unpriced updates (governor installed
    # mid-run, hops-only telemetry) must not deflate the mean.
    n_priced: int = 0
    # admission-control counters (bounded queue)
    n_offered: int = 0
    n_shed: int = 0
    # per-QoS-tier breakdown: tier label -> {n_done, n_shed, n_events,
    # total_pj, n_priced}.  Canary judging and gold-tier SLOs need the
    # split the fleet totals average away.
    tiers: dict = dataclasses.field(default_factory=dict)

    def _tier(self, tier: str) -> dict:
        t = self.tiers.get(tier)
        if t is None:
            t = self.tiers[tier] = {"n_done": 0, "n_shed": 0, "n_events": 0,
                                    "total_pj": 0.0, "n_priced": 0}
        return t

    def note_shed(self, tier: str = "default") -> None:
        self.n_shed += 1
        self._tier(tier)["n_shed"] += 1

    def note_done(self, tier: str = "default") -> None:
        self._tier(tier)["n_done"] += 1

    def note_done_many(self, counts: dict) -> None:
        """Batched :meth:`note_done`: ``{tier: completions}`` — one dict
        walk per harvest instead of a lookup per completed lane."""
        for tier, k in counts.items():
            self._tier(tier)["n_done"] += k

    def update(self, hops, energy_pj=None, tiers=None, names=None) -> None:
        """Fold one batch of decoded events in.  ``energy_pj`` may carry
        NaN for events nothing could price (a ledgered batch with an
        unledgered tenant) — only finite entries feed the energy totals.
        ``tiers`` optionally labels each event with its request's QoS tier
        for the per-tier breakdown: one label per event or, with
        ``names``, one integer code into ``names`` per event."""
        h = np.asarray(hops)
        self.total_hops += int(h.sum())
        self.n_events += int(h.size)
        priced = None
        if energy_pj is not None:
            e = np.asarray(energy_pj, np.float64)
            priced = np.isfinite(e)
            self.total_pj += float(e[priced].sum())
            self.n_priced += int(priced.sum())
            if priced.any():
                self.has_energy = True
        if tiers is None:
            return
        if names is None:
            index: dict[str, int] = {}
            tiers = [index.setdefault(t, len(index)) for t in tiers]
            names = list(index)
        codes = np.asarray(tiers, np.int64)
        n = np.bincount(codes, minlength=len(names))
        if priced is not None:
            n_priced = np.bincount(codes[priced], minlength=len(names))
            pj = np.bincount(codes[priced], weights=e[priced],
                             minlength=len(names))
        for c in np.flatnonzero(n).tolist():
            t = self._tier(names[c])
            t["n_events"] += int(n[c])
            if priced is not None and n_priced[c]:
                t["total_pj"] += float(pj[c])
                t["n_priced"] += int(n_priced[c])

    def tier_summary(self) -> dict:
        """{tier: {n_done, n_shed, n_events, mean_energy_nj}} — the
        per-tier view the fleet means hide."""
        return {tier: {"n_done": t["n_done"], "n_shed": t["n_shed"],
                       "n_events": t["n_events"],
                       "mean_energy_nj": t["total_pj"] * 1e-3
                       / max(1, t["n_priced"])}
                for tier, t in sorted(self.tiers.items())}

    def reset(self) -> None:
        self.total_hops = 0
        self.n_events = 0
        self.total_pj = 0.0
        self.has_energy = False
        self.n_priced = 0
        self.n_offered = 0
        self.n_shed = 0
        self.tiers = {}

    @property
    def mean_hops(self) -> float:
        return self.total_hops / max(1, self.n_events)

    @property
    def mean_energy_nj(self) -> float:
        """Mean modeled nJ per PRICED decoded event (0.0 until priced
        telemetry arrives).  Unpriced events (no governor / hops-only
        updates) are excluded from the denominator."""
        return self.total_pj * 1e-3 / max(1, self.n_priced)

    @property
    def shed_rate(self) -> float:
        """Fraction of offered requests shed by admission control."""
        return self.n_shed / max(1, self.n_offered)

    def summary(self, n_groves: int) -> str:
        s = (f"hops/event {self.mean_hops:.2f} "
             f"(grove fraction {self.mean_hops / max(1, n_groves):.2f}, "
             f"{self.n_events} events)")
        if self.has_energy:
            s += f", {self.mean_energy_nj:.3f} nJ/event"
        if self.n_shed:
            s += (f", shed {self.n_shed}/{self.n_offered} "
                  f"({100 * self.shed_rate:.1f}%)")
        return s


@dataclasses.dataclass
class SlotState:
    """A read view of one slot (``ContinuousBatcher.slots``)."""
    request: Request | None = None


def _runs(codes: np.ndarray) -> list:
    """``(code, indices)`` of each distinct code, in order of first
    appearance (one whole-slice run when every code is the same)."""
    if not codes.size:
        return []
    if (codes == codes[0]).all():
        return [(int(codes[0]), slice(None))]
    u, first = np.unique(codes, return_index=True)
    return [(int(c), np.flatnonzero(codes == c))
            for c in u[np.argsort(first)].tolist()]


class _Codes(dict):
    """Small-int codes of keys, assigned on first lookup
    (``names[code] == key``)."""

    def __init__(self):
        super().__init__()
        self.names: list = []

    def __missing__(self, key) -> int:
        c = self[key] = len(self.names)
        self.names.append(key)
        return c


class ContinuousBatcher:
    """Drives a dispatcher's replicas over a fixed slot batch, refilling
    as lanes finish.

    decode_fn: a host ``decode_fn(tokens [n_slots] int32, lengths
        [n_slots] int32, policy with per-lane [n_slots] knobs) ->
        (logits [n_slots, V], hops [n_slots])``, with ``bucket=`` as a
        keyword when requests name registry tenants (without it such
        requests are refused at submit); the batcher runs it
        behind a one-replica dispatcher
        (:func:`~repro.serve.dispatch.host_replica_factory`), priced by
        the governor or ledger.  Pass None with ``dispatcher``.
    prefill_fn(slot, prompt) -> int  (returns prompt length in cache);
        called by the host replica that ``decode_fn`` builds, unused with a
        ``dispatcher``.
    default_policy: applied to slots whose request carries no policy; its
        static knobs select the compiled program.
    governor: optional EnergyGovernor — when set, the *governor's active
        rung* replaces default_policy each step, per-step hop telemetry
        feeds its rolling estimate, and requests may carry
        ``energy_budget_nj`` contracts.  A TenantLedger instead governs
        each tenant apart.
    dispatcher: a :class:`~repro.serve.dispatch.DeviceDispatcher` — the
        execution plane.  Mutually exclusive with ``decode_fn``.
    max_queue: admission-control bound on the request queue (None =
        unbounded).
    shed_policy: who is shed when the queue is full — ``"reject"`` the
        incoming request (submit returns False) or evict the ``"oldest"``
        queued request.
    pipeline: harvest step t's dispatch at the start of step t+1, so host
        bookkeeping overlaps device compute (see :meth:`step`).
    telemetry_every: replay buffered telemetry every k harvests.

    Host instrumentation: ``phase_ns`` (always on) times the step's phases
    and the dispatcher's sub-phases in ns; ``route``, the refill's choice
    of replica, is part of ``refill``.  Setting ``trace = True`` wraps each
    in a ``serve.*`` profiler span, times Python's garbage-collector pauses
    into ``phase_ns["gc"]`` (one ``serve.gc`` span each) and sums into
    ``counts`` the walked and served lane-hops, the outputs harvested to
    the host, all and those whose copy started at enqueue, the requests
    admitted to each replica ``d`` (``routed_<d>``), the policy
    validations ``submit`` ran (``policy_checks``: once per distinct
    policy object), the admission groups refill bursts resolved
    (``admit_groups``) and the replicas' slot-state uploads and their
    bytes (``span_uploads``, ``span_upload_bytes``: one per replica a
    step that admitted or retired there, whatever its bucket calls); set
    it back to False to uninstall the collector callback.

    Per-lane state lives in arrays indexed by lane: the request, tokens
    left, serving bucket, tier and (tenant, version) codes.  Work that
    depends only on a request's (tenant, version, policy, tier) group —
    routing, knob and bucket resolution, telemetry folds — runs once per
    group per burst or harvest; only the ``Request`` fields the API
    returns are written per request.
    """

    def __init__(self, n_slots: int, decode_fn: Callable | None,
                 prefill_fn: Callable, eos_id: int = 1,
                 default_policy: FogPolicy | None = None,
                 governor=None, dispatcher=None,
                 max_queue: int | None = None, shed_policy: str = "reject",
                 registry=None, pipeline: bool = False,
                 telemetry_every: int = 1):
        self._n_slots = n_slots
        # the request in each lane (None = free)
        self._lane_req = np.full((n_slots,), None, object)
        self.queue: deque[Request] = deque()
        self.eos_id = eos_id
        self.completed: list[Request] = []
        self.default_policy = (default_policy if default_policy is not None
                               else FogPolicy())
        if self.default_policy.per_lane:
            raise ValueError(
                "default_policy must carry scalar knobs; the batcher "
                "assembles the per-lane vectors itself each step")
        # ``governor`` accepts either one EnergyGovernor (fleet-wide SLO)
        # or a TenantLedger (per-tenant SLOs, one governor per tenant)
        self.ledger = None
        if governor is not None and hasattr(governor, "governor_for"):
            self.ledger = governor
        elif governor is not None and governor.model is None:
            # a governor that can never act must be rejected loudly — a
            # silently unenforced SLO is worse than no governor at all
            raise ValueError(
                "the batcher's governor needs an energy model to price "
                "hop telemetry; construct EnergyGovernor(..., model=...)")
        self.governor = governor if self.ledger is None else None
        if dispatcher is None:
            if decode_fn is None:
                raise ValueError(
                    "decode_fn is required when no dispatcher is given")
            dispatcher = DeviceDispatcher(host_replica_factory(
                decode_fn, prefill_fn,
                price=lane_pricer(governor),
                policy=(self.governor.current if self.governor is not None
                        else self.default_policy)), jax.devices()[:1])
        elif decode_fn is not None:
            raise ValueError(
                "pass either decode_fn or dispatcher, not both (the "
                "dispatcher owns the per-device decode replicas)")
        dispatcher.bind(n_slots)
        self.dispatcher = dispatcher
        # can every replica serve (model, version) buckets?  A request
        # naming a model is refused at submit otherwise
        self._bucketed = all(r.bucketed for r in dispatcher.replicas)
        self.registry = registry
        if shed_policy not in ("reject", "oldest"):
            raise ValueError(f"unknown shed_policy {shed_policy!r}; "
                             "pick 'reject' or 'oldest'")
        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1 (or None = unbounded)")
        self.max_queue = max_queue
        self.shed_policy = shed_policy
        self.shed_requests: list[Request] = []
        # the dispatcher's drained Pending list from the last step (device /
        # precision / lane bookkeeping for the load harness)
        self.last_dispatches: list = []
        if telemetry_every < 1:
            raise ValueError("telemetry_every must be >= 1")
        self.pipeline = bool(pipeline)
        self.telemetry_every = int(telemetry_every)
        # occupancy mask + free-slot count per replica (the bound
        # dispatcher's spans): a burst takes the lowest free lanes of the
        # replica ``_route`` picks, and the step never walks all n_slots in
        # Python
        self._span = dispatcher.span
        self._n_free = [self._span] * (n_slots // self._span)
        self._route_last = len(self._n_free) - 1
        self._occ_mask = np.zeros((n_slots,), bool)
        self._n_active = 0
        # per lane: tokens left to generate, serving bucket id (-1 free),
        # tier code, (tenant, version) code, and whether the request
        # carries no policy of its own (restamped on rung moves); each
        # written once per admission group
        self._lane_left = np.zeros((n_slots,), np.int64)
        self._lane_bucket = np.full((n_slots,), -1, np.int32)
        self._lane_tier = np.zeros((n_slots,), np.int32)
        self._lane_mv = np.zeros((n_slots,), np.int32)
        self._lane_default = np.zeros((n_slots,), bool)
        # codes of (model, version, precision) buckets, tier labels,
        # (model, version) pairs and tenants
        self._buckets, self._tiers = _Codes(), _Codes()
        self._mvs, self._tenants = _Codes(), _Codes()
        # buckets holding lanes, in the order their first lane was admitted
        # (the dispatch order); an emptied bucket leaves at its next dispatch
        self._live: dict[tuple, int] = {}
        # policies submit() has validated, by identity (FogPolicy is frozen,
        # so a verdict cannot go stale; the value keeps the id unique)
        self._valid_policies: dict[int, FogPolicy] = {}
        self._inflight = False
        self._inflight_occ: np.ndarray | None = None
        self._tel_buf: list[tuple] = []
        self._steps_since_flush = 0
        # per-phase host-time accumulators (ns) for the step, the
        # dispatcher's sub-phases (shared dict) and, while tracing, the
        # collector's pauses
        self.phase_ns = dict.fromkeys(
            ("harvest", "bookkeep", "telemetry", "refill", "route",
             "dispatch", *PHASES, "gc"), 0)
        dispatcher.phase_ns = self.phase_ns
        self._routed = [f"routed_{d}" for d in range(len(self._n_free))]
        self.counts = dict.fromkeys(
            ("lane_hops_walked", "lane_hops_served", "host_copies",
             "host_copies_prefetched", *self._routed, "policy_checks",
             "admit_groups", "span_uploads", "span_upload_bytes"), 0)
        self._trace = False
        self._gc_open: tuple | None = None
        self.n_steps = 0
        # fleet-level FoG accounting: hop counts (and, with a governor's
        # energy model, modeled pJ) of every decoded token
        self.stats = ServeStats()

    @property
    def trace(self) -> bool:
        """Profiler spans, collector pauses and counters (see the
        class docstring); off by default."""
        return self._trace

    @trace.setter
    def trace(self, on: bool) -> None:
        on = bool(on)
        if on == self._trace:
            return
        self._trace = on
        self.dispatcher.trace = on
        if on:
            gc.callbacks.append(self._on_gc)
        else:
            gc.callbacks.remove(self._on_gc)
            self._gc_open = None

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            s = span("serve.gc", generation=info["generation"])
            s.__enter__()
            self._gc_open = (s, time.perf_counter_ns())
        elif self._gc_open is not None:
            s, t0 = self._gc_open
            self._gc_open = None
            self.phase_ns["gc"] += time.perf_counter_ns() - t0
            s.__exit__(None, None, None)

    @property
    def slots(self) -> list[SlotState]:
        """A read view of every slot: its request (None = free)."""
        return [SlotState(r) for r in self._lane_req.tolist()]

    def submit(self, req: Request) -> bool:
        """Validate, resolve energy contracts, then admit or shed.

        Returns True if the request was admitted to the queue, False if it
        was shed by admission control (``shed_policy="reject"`` with a full
        queue).  Invalid requests still raise — shedding is a load signal,
        not an error-swallowing path.
        """
        # stamp at the door: shed requests carry a submit time too (the
        # shed tail is part of the latency story), admitted requests keep
        # any pre-stamp the harness set
        if req.t_submit is None:
            req.t_submit = time.perf_counter()
        if req.model is not None:
            if self.registry is None and req.version is None:
                raise ValueError(
                    f"request {req.rid}: Request.model={req.model!r} needs "
                    "a registry to resolve the serving version (construct "
                    "ContinuousBatcher(..., registry=ModelRegistry(dir)), "
                    "or pre-set Request.version)")
            if not self._bucketed:
                raise ValueError(
                    f"request {req.rid}: Request.model={req.model!r} needs "
                    "a bucket-aware decode path (a decode_fn taking "
                    "bucket=) to route (model, version) buckets")
        if req.energy_budget_nj is not None:
            if req.policy is not None:
                raise ValueError(
                    f"request {req.rid}: pass either policy or "
                    "energy_budget_nj, not both (the budget is resolved "
                    "into a policy)")
            gov = self.governor
            if gov is None and self.ledger is not None:
                gov = self.ledger.governor_for(req.tenant)
            if gov is None:
                raise ValueError(
                    f"request {req.rid}: energy_budget_nj needs a "
                    "governor (construct ContinuousBatcher(..., "
                    "governor=EnergyGovernor(frontier, ...)) or ledger "
                    "an EnergyGovernor for this request's tenant)")
            pol = gov.policy_for_budget(req.energy_budget_nj)
            # the per-request contract is the per-lane/bucketed knobs only
            # (threshold, hop budget, precision); any static knobs the
            # ladder rung inherited from the fleet default (backend,
            # max_hops, ...) stay with the fleet default — they select the
            # compiled program and would otherwise trip the static-knob
            # rejection below.  A fresh policy per request: not cached.
            req.policy = FogPolicy(threshold=pol.threshold,
                                   hop_budget=pol.hop_budget,
                                   precision=pol.precision)
            self._check_policy(req.rid, req.policy)
        elif (req.policy is not None and self._valid_policies.get(
                id(req.policy)) is not req.policy):
            self._check_policy(req.rid, req.policy)
            cache = self._valid_policies
            if len(cache) >= 64:
                del cache[next(iter(cache))]
            cache[id(req.policy)] = req.policy
        self.stats.n_offered += 1
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            if self.shed_policy == "reject":
                self._shed(req)
                return False
            # "oldest": evict the head of the queue to admit the newcomer
            self._shed(self.queue.popleft())
        self.queue.append(req)
        return True

    def _check_policy(self, rid, pol: FogPolicy) -> None:
        """Raise unless ``pol`` is a valid per-request contract: scalar
        knobs, and no static knob but precision."""
        if self._trace:
            self.counts["policy_checks"] += 1
        if pol.per_lane:
            raise ValueError(
                f"request {rid}: per-request policies are scalar "
                "contracts; the batcher assembles the per-lane vectors")
        # precision is static too, but the batcher handles it by
        # dispatching one program per precision group (see step())
        rejected = tuple(k for k in pol.static_overrides
                         if k != "precision")
        if rejected:
            raise ValueError(
                f"request {rid}: policy sets static knobs "
                f"{rejected} — those select the "
                "compiled program and cannot vary per request; set "
                "them on the batcher's default_policy (per-request "
                "knobs are threshold, hop_budget and precision)")

    def _shed(self, req: Request) -> None:
        req.shed = True
        self.shed_requests.append(req)
        self.stats.note_shed(req.tier)

    def _route(self, n_queued: int) -> list[int]:
        """The replicas a refill burst takes its slots from, in order: the
        one with the most free slots (ties go in turn after the replica used
        last), then the others in turn after it.  The burst takes the
        lowest free slots of the first and spills into the next only when
        that one is full, so a step touches one replica while it has room
        and the replicas take turns.  One replica: always itself, lowest
        free slot first.  Times itself into ``phase_ns["route"]``;
        with ``trace`` on, one ``serve.route`` span (``replica``,
        ``admitted``, ``spilled``)."""
        frees = self._n_free
        n = len(frees)
        with (span("serve.route") if self._trace else NO_SPAN) as s:
            t0 = time.perf_counter_ns()
            if n == 1:
                order = [0]
            else:
                last, best, most = self._route_last, 0, -1
                for k in range(1, n + 1):
                    d = (last + k) % n
                    if frees[d] > most:
                        best, most = d, frees[d]
                order = [(best + k) % n for k in range(n)]
            self.phase_ns["route"] += time.perf_counter_ns() - t0
            if s is not None:
                admitted = min(n_queued, sum(frees))
                s.set_metadata(replica=order[0], admitted=admitted,
                               spilled=max(0, admitted - frees[order[0]]))
        return order

    def _take_lanes(self, k: int) -> np.ndarray:
        """The lanes of a refill burst of ``k`` queued requests (``k`` at
        most the free slots), in admission order: the lowest free lanes of
        the replicas ``_route`` gives, spilling into the next only when one
        is full.  Marks them occupied; notes the replica used last and,
        with ``trace`` on, ``routed_<d>``."""
        occ, width, n_free = self._occ_mask, self._span, self._n_free
        parts = []
        for d in self._route(k):
            m = min(k, n_free[d])
            if m:
                lo = d * width
                parts.append(np.flatnonzero(~occ[lo:lo + width])[:m] + lo)
                n_free[d] -= m
                k -= m
                self._route_last = d
                if self._trace:
                    self.counts[self._routed[d]] += m
                if not k:
                    break
        lanes = parts[0] if len(parts) == 1 else np.concatenate(parts)
        occ[lanes] = True
        self._n_active += len(lanes)
        return lanes

    def _free_lanes(self, lanes: np.ndarray) -> None:
        """Return ``lanes`` to their replicas' free slots."""
        self._occ_mask[lanes] = False
        self._lane_req[lanes] = None
        self._lane_bucket[lanes] = -1
        self._n_active -= len(lanes)
        per = np.bincount(lanes // self._span, minlength=len(self._n_free))
        for d in np.flatnonzero(per).tolist():
            self._n_free[d] += int(per[d])

    @property
    def active(self) -> int:
        return self._n_active

    def _tenant_rung(self, tenant: str | None) -> FogPolicy | None:
        """The ledgered rung policy billing ``tenant`` (None when no
        ledger, or the ledger knows neither tenant nor default)."""
        if self.ledger is None:
            return None
        gov = self.ledger.governor_for(tenant)
        return None if gov is None else gov.current

    # -- the serving step ---------------------------------------------------
    #
    # Per-slot state stays with the replicas: feature rows (or tokens and
    # cache lengths) and per-lane policy knobs are staged at admission and
    # retirement, each bucket dispatch carries only the step's default-rung
    # scalars, and one call per replica returns (next, hops, energy) for
    # its span.  ``pipeline=True`` double-buffers the loop: step t's
    # dispatch is harvested at the START of step t+1, so the host's
    # refill/splice/bookkeeping for t+1 overlaps device compute of t.  The
    # request -> (slot, dispatch) mapping is IDENTICAL to the synchronous
    # mode (completions are processed before the next refill in both), so
    # the pipelined path is bit-equivalent under a fixed seed — only the
    # wall-clock interleaving changes (see tests/test_serve_equivalence).
    #
    # Telemetry is buffered and replayed in order every ``telemetry_every``
    # steps (and at :meth:`flush`): the governor/ledger/registry see
    # exactly the per-step batches they would have seen live, just later —
    # rung transitions therefore take effect at flush boundaries.

    def step(self) -> int:
        """One decode step across all active slots.  Returns #active.

        With ``pipeline=True`` this harvests the PREVIOUS step's dispatch
        and issues this step's — completions surface one ``step()`` call
        later; :meth:`flush` drains the tail.
        """
        pc = time.perf_counter_ns
        tr = self._trace
        with (span("serve.step", step=self.n_steps) if tr else NO_SPAN):
            if self._inflight:
                self._process_harvest()
            with (span("serve.refill", admitted=min(
                    len(self.queue), self._n_slots - self._n_active))
                  if tr else NO_SPAN):
                t1 = pc()
                self._refill()
                t2 = pc()
            self.phase_ns["refill"] += t2 - t1
            if self._n_active:
                with (span("serve.dispatch") if tr else NO_SPAN):
                    self._dispatch()
                    t3 = pc()
                self.phase_ns["dispatch"] += t3 - t2
                if not self.pipeline:
                    self._process_harvest()
        self.n_steps += 1
        return self._n_active

    def _refill(self) -> None:
        q = self.queue
        k = min(len(q), self._n_slots - self._n_active)
        if not k:
            return
        now = time.perf_counter()
        lanes = self._take_lanes(k)
        popleft = q.popleft
        reqs = [popleft() for _ in range(k)]
        # what is read or written per request: the admission stamp, the
        # tokens left to generate and the request's group
        for r in reqs:
            r.t_admit = now
        left = [r.max_new_tokens - len(r.generated) for r in reqs]
        keys = [(r.model, r.version, id(r.policy), r.tier) for r in reqs]
        groups = {key: g for g, key in enumerate(dict.fromkeys(keys))}
        if len(groups) > 1:
            gid = np.fromiter(map(groups.__getitem__, keys), np.int64, k)
        # each group's positions in the burst, split by serving version
        registry = self.registry
        parts = []
        for g in range(len(groups)):
            if len(groups) == 1:
                pos, greqs = np.arange(k), reqs
            else:
                pos = np.flatnonzero(gid == g)
                greqs = [reqs[j] for j in pos.tolist()]
            r0 = greqs[0]
            if (r0.model is None or r0.version is not None
                    or registry is None):
                parts.append((pos, r0, r0.version))
            elif registry.canary(r0.model) is None:
                # pin the serving version at slot assignment (a hot-swap
                # never migrates in-flight work): one live version for the
                # whole group while the tenant runs no canary split
                v = registry.live_version(r0.model)
                for r in greqs:
                    r.version = v
                parts.append((pos, r0, v))
            else:
                vs = np.asarray([registry.route(r0.model, r.rid)
                                 for r in greqs])
                for r, v in zip(greqs, vs.tolist()):
                    r.version = v
                for v, idx in _runs(vs):
                    parts.append((pos[idx], r0, v))
        # resolve each part once, in order of its first request, so serving
        # buckets join the dispatch order as they did lane by lane
        parts.sort(key=lambda p: p[0][0])
        thr = np.empty((k,), np.float32)
        bud = np.empty((k,), np.int32)
        for pos, r0, version in parts:
            pol = r0.policy
            if pol is None:
                # tenant-ledger lanes are stamped CONCRETE at their tenant's
                # current rung (re-stamped when a flush transitions that
                # governor); fleet-default lanes stay sentinels and track
                # the rung in-jit every dispatch
                pol = self._tenant_rung(r0.tenant)
            thr[pos], bud[pos] = lane_knobs(pol)
            prec = None if pol is None else pol.precision
            glanes = lanes[pos]
            self._lane_bucket[glanes] = self._bucket_of(
                (r0.model, version, prec))
            self._lane_tier[glanes] = self._tiers[r0.tier]
            self._lane_mv[glanes] = self._mvs[r0.model, version]
            self._lane_default[glanes] = r0.policy is None
        if self._trace:
            self.counts["admit_groups"] += len(parts)
        self._lane_req[lanes] = np.fromiter(reqs, object, k)
        self._lane_left[lanes] = left
        # one staging write per replica for the whole burst
        self.dispatcher.admit_lanes(lanes, [r.prompt for r in reqs], thr,
                                    bud)

    def _bucket_of(self, key: tuple) -> int:
        """The id of serving bucket ``key`` (model, version, precision);
        a bucket that holds no lanes joins the end of the dispatch order."""
        return self._live.setdefault(key, self._buckets[key])

    def _dispatch(self) -> None:
        default = (self.governor.current if self.governor is not None
                   else self.default_policy)
        def_thr = float(np.asarray(default.threshold))
        def_bud = (int(np.asarray(default.hop_budget))
                   if default.hop_budget is not None else NO_BUDGET)
        for key, bid in list(self._live.items()):
            lanes = np.flatnonzero(self._lane_bucket == bid)
            if not lanes.size:
                del self._live[key]
                continue
            model, version, prec = key
            eff_prec = prec if prec is not None else default.precision
            bucket = None if model is None else (model, version)
            self.dispatcher.dispatch_packed(
                lanes, def_thr, def_bud, precision=eff_prec, bucket=bucket)
        self._inflight = True
        self._inflight_occ = np.flatnonzero(self._occ_mask)

    def _process_harvest(self) -> None:
        pc = time.perf_counter_ns
        tr = self._trace
        with (span("serve.harvest") if tr else NO_SPAN):
            t0 = pc()
            nxt, hops, energy, pend = self.dispatcher.harvest_packed(
                self._n_slots, counts=self.counts if tr else None)
            self.last_dispatches = pend
            self._inflight = False
            occ = self._inflight_occ
            t1 = pc()
        self.phase_ns["harvest"] += t1 - t0
        with (span("serve.bookkeep") if tr else NO_SPAN):
            # the lanes' codes, read before their retirement clears them
            hops, tiers = hops[occ], self._lane_tier[occ]
            mvs = self._lane_mv[occ]
            self._bookkeep(occ, nxt[occ], hops, tiers)
            t2 = pc()
        self.phase_ns["bookkeep"] += t2 - t1
        with (span("serve.telemetry") if tr else NO_SPAN):
            if occ.size:
                self._tel_buf.append((hops, energy[occ], tiers, mvs, occ))
                self._steps_since_flush += 1
                if self._steps_since_flush >= self.telemetry_every:
                    self._flush_telemetry()
            t3 = pc()
        self.phase_ns["telemetry"] += t3 - t2

    def _bookkeep(self, occ, nxt, hops, tiers) -> None:
        """Fold one harvest (``nxt``, ``hops`` and tier codes of the
        occupied lanes ``occ``, ascending) into their requests; complete
        the finished ones in lane order and retire their lanes in bulk."""
        now = time.perf_counter()
        reqs = self._lane_req[occ].tolist()
        left = self._lane_left[occ] - 1
        self._lane_left[occ] = left
        for r, tok, h in zip(reqs, nxt.tolist(), hops.tolist()):
            r.generated.append(tok)
            r.hops.append(h)
        done = (nxt == self.eos_id) | (left <= 0)
        if not done.any():
            return
        if done.all():
            fin, gone, codes = reqs, occ, tiers
        else:
            idx = np.flatnonzero(done)
            fin = [reqs[j] for j in idx.tolist()]
            gone, codes = occ[idx], tiers[idx]
        for r in fin:
            r.done = True
            if r.t_submit is not None:
                r.t_done = now
        self.completed.extend(fin)
        names = self._tiers.names
        n = np.bincount(codes, minlength=len(names))
        self.stats.note_done_many({names[c]: int(n[c])
                                   for c in np.flatnonzero(n).tolist()})
        self._free_lanes(gone)
        # one bulk dead-stamp per replica (an admit in the same step
        # simply overwrites the staged entry)
        self.dispatcher.retire_lanes(gone)

    def _flush_telemetry(self) -> None:
        """Replay the buffered per-step telemetry batches IN ORDER: the
        governor/ledger observe+step per batch exactly as the inline path
        would have, the fleet stats and registry per-version stats fold in
        the same events — deferral changes when the consumers see the
        telemetry (flush boundaries), never what they see.  Each batch
        is folded per tier, tenant and (tenant, version) code."""
        buf, self._tel_buf = self._tel_buf, []
        self._steps_since_flush = 0
        if not buf:
            return
        ledger, registry = self.ledger, self.registry
        names, mv_keys = self._tiers.names, self._mvs.names
        ledger_trans = None
        if ledger is not None:
            govs = [g for _, g in ledger.items()]
            if ledger.default is not None:
                govs.append(ledger.default)
            ledger_trans = [(g, len(g.transitions)) for g in govs]
            tenant_of = np.asarray([self._tenants[m] for m, _ in mv_keys],
                                   np.int64)
        fleet_batches = []
        for hops, energy, tiers, mvs, lanes in buf:
            e = energy
            if self.governor is not None:
                fleet_batches.append(
                    (e, self.dispatcher.lane_devices(lanes)))
            elif ledger is not None:
                # per-tenant governance, NaN for lanes no governor bills
                devices = self.dispatcher.lane_devices(lanes)
                e = energy.copy()
                for c, idx in _runs(tenant_of[mvs]):
                    gov = ledger.governor_for(self._tenants.names[c])
                    if gov is None:
                        e[idx] = np.nan
                        continue
                    gov.ingest([(e[idx], devices[idx])])
            self.stats.update(hops, e, tiers=tiers, names=names)
            if registry is not None:
                for c, idx in _runs(mvs):
                    model, version = mv_keys[c]
                    if model is not None and version is not None:
                        registry.stats_for(model, version).update(
                            hops[idx], e[idx], tiers=tiers[idx],
                            names=names)
        if self.governor is not None and fleet_batches:
            self.governor.ingest(fleet_batches)
        if ledger_trans is not None and any(
                len(g.transitions) != n for g, n in ledger_trans):
            # a tenant rung moved: its concrete lane stamps (and rung-
            # precision bucket keys) are stale — re-stamp the occupied
            # default-policy lanes
            self._restamp_default_lanes()

    def _restamp_default_lanes(self) -> None:
        """Stamp the occupied lanes without a policy of their own at their
        tenant's current rung, one (tenant, version) group at a time."""
        lanes = np.flatnonzero(self._occ_mask & self._lane_default)
        thr = np.empty((len(lanes),), np.float32)
        bud = np.empty((len(lanes),), np.int32)
        keep = np.zeros((len(lanes),), bool)
        for c, idx in _runs(self._lane_mv[lanes]):
            model, version = self._mvs.names[c]
            rung = self._tenant_rung(model)
            if rung is None:
                continue
            thr[idx], bud[idx] = lane_knobs(rung)
            keep[idx] = True
            self._lane_bucket[lanes[idx]] = self._bucket_of(
                (model, version, rung.precision))
        if keep.any():
            self.dispatcher.admit_lanes(lanes[keep], None, thr[keep],
                                        bud[keep])

    def flush(self) -> None:
        """Drain the pipelined loop: harvest any in-flight dispatch, then
        replay ALL buffered telemetry.  After flush() the governor/ledger/
        registry/stats state is exactly what the synchronous per-step loop
        would hold — call it before reading telemetry mid-run and once
        after the last step."""
        if self._inflight:
            self._process_harvest()
        self._flush_telemetry()

    def run(self, max_steps: int = 10000) -> list[Request]:
        steps = 0
        while (self.queue or self.active) and steps < max_steps:
            self.step()
            steps += 1
        self.flush()
        return self.completed

"""Data-parallel serving plane: replicate the model, shard the batch.

The ring backend shards *groves* across a mesh; this module shards the
*batch*.  A :class:`DeviceDispatcher` owns N replicas of one decode
program, each bound to its own device and to a fixed contiguous span of
the batcher's slots (slot ``i`` lives on device ``i // span`` forever, so
per-device state — packed tables, KV caches, feature buffers — never
migrates and every replica compiles exactly one program shape).  Which
replica a request lands on is the batcher's choice: its router gives each
refill burst the slots of one replica, the one with the most free slots,
in turn (:meth:`~repro.serve.scheduler.ContinuousBatcher._route`), so a
step's dispatch touches one replica while that replica has room.

Dispatch is asynchronous: each step the
:class:`~repro.serve.scheduler.ContinuousBatcher` calls
:meth:`DeviceDispatcher.dispatch` once per precision group; the dispatcher
slices the group's span inputs, enqueues one decode call per (device,
precision) on that device's dispatch queue, and returns WITHOUT blocking —
JAX's async dispatch lets every replica compute concurrently.
:meth:`harvest` drains the queues with a single deferred
``jax.block_until_ready`` over everything in flight, then scatters the
per-span outputs back into full ``[n_slots]`` arrays.  A precision group
that touches a span dispatches the FULL span (fixed shape, no recompile
churn — the per-lane threshold/budget vectors are traced inputs) and only
the group's lanes are harvested from it, mirroring the single-device
bucketed dispatch in ``scheduler.step``.

Replication is plain device placement: :func:`replicate` ``device_put``\\ s
a pytree (e.g. a :class:`~repro.forest.pack.ForestPack`) onto each serve
device; committed inputs then pin each replica's computation to its own
device.  On CPU-only hosts (CI), force a mesh with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — see
:func:`repro.launch.mesh.serve_devices`.

:class:`ForestReplicaServer` is the canonical factory for the paper's
workload: forest classification serving, one pending feature row per slot,
a ForestPack replica (per precision) per device.
"""
from __future__ import annotations

import dataclasses
import time
from contextlib import nullcontext
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import NO_BUDGET, FogPolicy, LanePolicies
from repro.kernels.fused_fog import walked_lane_hops
from repro.kernels.tree_traverse import lane_block

# the packed serving path's fused kernel compacts live lanes every hop
# (the engine's default); the lane-hop counter follows the same walk
SERVE_COMPACT = True
# host sub-phases the dispatcher times into the batcher's ``phase_ns``
PHASES = ("harvest_wait", "harvest_copy", "splice", "enqueue")
NO_SPAN = nullcontext()


def span(name: str, **args):
    """A host span on the profiler's clock (``jax.profiler.TraceAnnotation``;
    ``args`` become the span's statistics).  Built only where tracing is
    on: callers write ``with (span(...) if trace else NO_SPAN):``."""
    return jax.profiler.TraceAnnotation(name, **args)


def replicate(tree, devices: Sequence) -> list:
    """One committed copy of ``tree`` per device (model replication for the
    data-parallel plane)."""
    return [jax.device_put(tree, d) for d in devices]


@dataclasses.dataclass
class Pending:
    """One in-flight decode call on one device's dispatch queue."""

    device: int                  # dispatcher device index
    precision: str | None        # the precision group this call serves
    lanes: np.ndarray            # global lane indices to harvest from it
    local: np.ndarray            # those lanes' offsets inside the span
    logits: object               # [span, C] device array (not yet ready)
    hops: object                 # [span] device array | None
    # the (model, version) registry bucket this call serves (None = the
    # single built-in model)
    bucket: tuple | None = None
    # packed-protocol outputs (the resident fast path): argmax labels and
    # per-lane modeled pJ computed inside the dispatch, so harvest never
    # downloads [span, C] logits or re-prices hops on the host
    nxt: object | None = None    # [span] int32 device array
    energy: object | None = None  # [span] float32 device array
    # outputs whose device-to-host copy was started at enqueue
    prefetched: int = 0


class DeviceDispatcher:
    """Fan one continuous batch out over per-device decode replicas.

    decode_factory(index, device, span) -> decode_fn(tokens [span],
        lengths [span], policy with [span] lane vectors) -> (logits, hops)
        The factory builds ONE replica: it places that replica's state on
        ``device`` and must return without blocking on results (outputs are
        harvested later).  ``tokens``/``lengths`` arrive as numpy slices;
        the replica is responsible for ``jax.device_put`` onto its device.
    devices: the serve devices (default: every local device — force >1 on
        CPU via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

    The dispatcher is bound to a slot count by the batcher
    (:meth:`bind`); ``n_slots`` must divide evenly over the devices.  The
    batcher reads ``span`` to count the free slots of each replica and
    routes each refill burst to one of them; the dispatcher calls only the
    replicas whose spans a dispatch's lanes touch.

    ``phase_ns`` accumulates the packed cycle's host sub-phases (``PHASES``;
    a batcher shares its own dict here) and ``trace`` (set by the batcher)
    wraps each in a ``serve.dispatch.*`` / ``serve.harvest.*`` span.
    """

    def __init__(self, decode_factory: Callable, devices: Sequence | None = None):
        if devices is None:
            devices = jax.devices()
        if not devices:
            raise ValueError("DeviceDispatcher needs at least one device")
        self.devices = list(devices)
        self.decode_factory = decode_factory
        self.span: int | None = None
        self._fns: list[Callable] | None = None
        # per-device dispatch queues, drained at harvest time
        self._queues: list[list[Pending]] = [[] for _ in self.devices]
        self.phase_ns = dict.fromkeys(PHASES, 0)
        self.trace = False

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def bind(self, n_slots: int) -> None:
        """Partition ``n_slots`` into per-device spans and build the
        replicas (idempotent for the same slot count)."""
        if self.span is not None:
            if self.span * self.n_devices != n_slots:
                raise ValueError(
                    f"dispatcher already bound to "
                    f"{self.span * self.n_devices} slots, cannot rebind "
                    f"to {n_slots}")
            return
        if n_slots % self.n_devices:
            raise ValueError(
                f"n_slots={n_slots} must divide evenly over "
                f"{self.n_devices} devices (fixed per-device spans)")
        self.span = n_slots // self.n_devices
        self._fns = [self.decode_factory(i, d, self.span)
                     for i, d in enumerate(self.devices)]
        from repro.serve.scheduler import _takes_bucket
        self._fn_buckets = [_takes_bucket(fn) for fn in self._fns]

    @property
    def replicas(self) -> list:
        """The bound per-device decode callables, in device order."""
        if self._fns is None:
            raise ValueError("dispatcher not bound; construct the batcher "
                             "with it first")
        return list(self._fns)

    @property
    def bucket_aware(self) -> bool:
        """Can the replicas route (model, version) buckets?  True only
        when EVERY replica decode fn takes a ``bucket`` keyword."""
        if self._fns is None:
            raise ValueError("dispatcher not bound; construct the batcher "
                             "(or call bind) first")
        return all(self._fn_buckets)

    def device_of(self, lane: int) -> int:
        """Which device serves a global lane index."""
        if self.span is None:
            raise ValueError("dispatcher not bound; construct the batcher "
                             "(or call bind) first")
        return lane // self.span

    def lane_devices(self, lanes) -> np.ndarray:
        """Vectorized :meth:`device_of` (telemetry labeling)."""
        return np.asarray(lanes, np.int64) // self.span

    # -- the dispatch/harvest cycle ---------------------------------------
    def dispatch(self, tokens: np.ndarray, lengths: np.ndarray,
                 policy: FogPolicy, lanes,
                 bucket: tuple | None = None) -> list[Pending]:
        """Enqueue one bucket's lanes, without blocking.

        ``policy`` carries the group's static knobs and the FULL-batch
        per-lane vectors; ``lanes`` are the global lane indices belonging
        to this group; ``bucket`` is the (model, version) registry bucket
        (None = the single built-in model).  Every device whose span
        intersects ``lanes`` gets one decode call over its whole span.
        """
        if self._fns is None:
            self.bind(len(tokens))
        lanes = np.asarray(lanes, np.int64)
        thr = np.asarray(policy.threshold)
        bud = (np.asarray(policy.hop_budget)
               if policy.hop_budget is not None else None)
        out = []
        for d in np.unique(lanes // self.span):
            d = int(d)
            lo, hi = d * self.span, (d + 1) * self.span
            sl = slice(lo, hi)
            span_pol = policy.replace(
                threshold=thr[sl] if thr.ndim else policy.threshold,
                hop_budget=(bud[sl] if bud is not None and bud.ndim
                            else policy.hop_budget))
            mine = lanes[(lanes >= lo) & (lanes < hi)]
            if self._fn_buckets[d]:
                logits, hops = self._fns[d](tokens[sl], lengths[sl],
                                            span_pol, bucket=bucket)
            elif bucket is not None:
                raise ValueError(
                    f"device {d}'s decode replica is not bucket-aware "
                    "(no bucket= parameter) but the batch carries "
                    f"registry bucket {bucket!r}")
            else:
                logits, hops = self._fns[d](tokens[sl], lengths[sl],
                                            span_pol)
            p = Pending(device=d, precision=policy.precision, lanes=mine,
                        local=mine - lo, logits=logits, hops=hops,
                        bucket=bucket)
            self._queues[d].append(p)
            out.append(p)
        return out

    # -- the packed (device-resident) cycle -------------------------------
    @property
    def packed(self) -> bool:
        """True when every replica speaks the packed protocol: resident
        slot state updated via :meth:`admit_lane` / :meth:`retire_lane`
        splices, dispatches that take only the step's default knobs, and
        ``(next, hops, energy)`` outputs (see
        :meth:`ForestReplicaServer.packed_factory`)."""
        if self._fns is None:
            raise ValueError("dispatcher not bound; construct the batcher "
                             "(or call bind) first")
        return all(getattr(fn, "packed", False) for fn in self._fns)

    def admit_lane(self, lane: int, row, thr: float, bud: int) -> None:
        """Stage one lane's feature row + resolved policy knobs onto its
        replica (applied as a donated device splice at the next dispatch).
        ``row=None`` re-stamps the policy knobs only (rung re-stamps after
        a deferred-telemetry flush)."""
        self._fns[lane // self.span].admit(lane % self.span, row, thr, bud)

    def admit_lanes(self, lanes, rows, thr, bud) -> None:
        """Bulk :meth:`admit_lane`: one vectorized staging write per
        intersecting replica instead of a Python call per lane.  ``rows``
        is ``[k, n_features]`` aligned with ``lanes`` (or None for a
        knob-only re-stamp); ``thr`` / ``bud`` are ``[k]``."""
        lanes = np.asarray(lanes, np.int64)
        thr = np.asarray(thr, np.float32)
        bud = np.asarray(bud, np.int32)
        devs = lanes // self.span
        for d in np.unique(devs):
            m = devs == d
            self._fns[int(d)].admit_many(
                lanes[m] - int(d) * self.span,
                None if rows is None else rows[m], thr[m], bud[m])

    def retire_lane(self, lane: int) -> None:
        """Stage one lane DEAD on its replica (freed slot: exits on hop 1
        until re-admitted; an admit in the same step overrides it)."""
        self._fns[lane // self.span].retire(lane % self.span)

    def retire_lanes(self, lanes) -> None:
        """Bulk :meth:`retire_lane` (one staging write per replica)."""
        lanes = np.asarray(lanes, np.int64)
        devs = lanes // self.span
        for d in np.unique(devs):
            self._fns[int(d)].retire_many(
                lanes[devs == d] - int(d) * self.span)

    def dispatch_packed(self, lanes, default_thresh: float,
                        default_budget: int, precision: str | None = None,
                        bucket: tuple | None = None) -> list[Pending]:
        """Enqueue one bucket's lanes on the packed protocol, without
        blocking: every intersecting device runs its whole span from
        RESIDENT state — the only per-dispatch traced inputs are the step's
        default threshold/budget scalars (lanes without explicit policies
        resolve against them in-jit, so a governor rung change costs no
        re-splice)."""
        lanes = np.fromiter(lanes, np.int64, len(lanes)) \
            if not isinstance(lanes, np.ndarray) else lanes.astype(np.int64)
        pc, ph, tr = time.perf_counter_ns, self.phase_ns, self.trace
        out = []
        for d in np.unique(lanes // self.span):
            d = int(d)
            lo = d * self.span
            mine = lanes[(lanes >= lo) & (lanes < lo + self.span)]
            fn = self._fns[d]
            with (span("serve.dispatch.splice", replica=d) if tr
                  else NO_SPAN):
                t0 = pc()
                fn.splice()
                t1 = pc()
            with (span("serve.dispatch.enqueue", replica=d,
                       lanes=len(mine), precision=precision) if tr
                  else NO_SPAN):
                outs = fn.enqueue(
                    np.float32(default_thresh), np.int32(default_budget),
                    precision, bucket=bucket)
                # every packed output goes to the host at harvest: start
                # its copy now, queued on the device behind the kernel, so
                # the harvest finds the bytes there instead of paying one
                # synchronous round trip per array
                with (span("serve.dispatch.prefetch", replica=d) if tr
                      else NO_SPAN):
                    for a in outs:
                        a.copy_to_host_async()
                t2 = pc()
            ph["splice"] += t1 - t0
            ph["enqueue"] += t2 - t1
            nxt, hops, energy = outs
            p = Pending(device=d, precision=precision, lanes=mine,
                        local=mine - lo, logits=None, hops=hops,
                        bucket=bucket, nxt=nxt, energy=energy,
                        prefetched=len(outs))
            self._queues[d].append(p)
            out.append(p)
        return out

    def harvest_packed(self, n_slots: int, counts: dict | None = None):
        """Drain the packed queues: one deferred ``block_until_ready``,
        then scatter each group's lanes into full-batch HOST arrays (their
        copies were started at enqueue, see :meth:`dispatch_packed`).

        Returns ``(next [n_slots] int32, hops [n_slots] int64,
        energy_pj [n_slots] float64, dispatches)`` — no logits cross the
        host boundary and nothing is re-priced here.  With ``counts``, adds
        the lane-hops the fused kernel walked over each call's whole span
        (``lane_hops_walked``) and those of the lanes harvested from it
        (``lane_hops_served``), the outputs harvested (``host_copies``) and
        those whose copy was started at enqueue
        (``host_copies_prefetched``)."""
        pending = [p for q in self._queues for p in q]
        for q in self._queues:
            q.clear()
        if not pending:
            raise ValueError("harvest_packed() with nothing dispatched")
        pc, ph, tr = time.perf_counter_ns, self.phase_ns, self.trace
        with (span("serve.harvest.wait", pending=len(pending)) if tr
              else NO_SPAN):
            t0 = pc()
            jax.block_until_ready([(p.nxt, p.hops, p.energy)
                                   for p in pending])
            t1 = pc()
        with (span("serve.harvest.copy", pending=len(pending)) if tr
              else NO_SPAN):
            nxt = np.zeros((n_slots,), np.int32)
            hops = np.zeros((n_slots,), np.int64)
            energy = np.zeros((n_slots,), np.float64)
            span_hops = []
            for p in pending:
                h = np.asarray(p.hops)
                span_hops.append(h)
                nxt[p.lanes] = np.asarray(p.nxt)[p.local]
                hops[p.lanes] = h[p.local]
                energy[p.lanes] = np.asarray(p.energy)[p.local]
            t2 = pc()
        ph["harvest_wait"] += t1 - t0
        ph["harvest_copy"] += t2 - t1
        if counts is not None:
            for p, h in zip(pending, span_hops):
                fn = self._fns[p.device]
                counts["lane_hops_walked"] += walked_lane_hops(
                    h, fn.block_b, fn.compact)
                counts["lane_hops_served"] += int(h[p.local].sum())
                counts["host_copies"] += 3      # nxt, hops, energy
                counts["host_copies_prefetched"] += p.prefetched
        return nxt, hops, energy, pending

    def harvest(self, n_slots: int):
        """Drain every device queue: ONE deferred ``block_until_ready``
        over all in-flight outputs, then scatter the group lanes back into
        full-batch arrays.

        Returns ``(logits [n_slots, C], hops [n_slots] | None,
        dispatches)`` — logits/hops as HOST numpy arrays — where
        ``dispatches`` is the drained :class:`Pending` list (device /
        precision / lane bookkeeping for telemetry and the load harness's
        per-device accounting).
        """
        pending = [p for q in self._queues for p in q]
        for q in self._queues:
            q.clear()
        if not pending:
            raise ValueError("harvest() with nothing dispatched")
        # the single deferred synchronization point of the whole step
        jax.block_until_ready([(p.logits, p.hops) for p in pending])
        hops_present = [p.hops is not None for p in pending]
        if any(hops_present) != all(hops_present):
            raise ValueError(
                "inconsistent decode replicas: some returned hop telemetry "
                "and some returned hops=None")
        logits = None
        hops = None
        for p in pending:
            lg = np.asarray(p.logits)
            if logits is None:
                logits = np.zeros((n_slots,) + lg.shape[1:], lg.dtype)
                if p.hops is not None:
                    hops = np.zeros((n_slots,), np.int64)
            logits[p.lanes] = lg[p.local]
            if p.hops is not None:
                hops[p.lanes] = np.asarray(p.hops)[p.local]
        # numpy on purpose: the scheduler's post-step bookkeeping (argmax,
        # per-lane harvesting) is host-side serial work — handing back
        # device arrays would buy nothing but re-dispatch latency
        return logits, hops, pending


@partial(jax.jit,
         static_argnames=("max_hops", "backend", "block_b"))
def _serve_eval(pack, x, key, step, thresh, budget, max_hops: int,
                backend: str, block_b: int):
    """One span's decode as ONE jitted program: start-grove draw +
    Algorithm-2 evaluation fused into a single dispatch.  The serving loop
    is latency-bound on per-dispatch Python/runtime overhead, so the
    un-jitted conveniences of ``FogEngine.eval`` (policy resolution, report
    pricing, a separate ``sample_starts`` dispatch) are deliberately
    bypassed — ``_eval_core`` is the same conformance-tested state machine
    every backend shares."""
    from repro.core.engine import _eval_core
    start = jax.random.randint(jax.random.fold_in(key, step),
                               (x.shape[0],), 0, pack.n_groves)
    res = _eval_core(pack, x, start, thresh, budget, max_hops, backend,
                     block_b, False)
    return res.proba, res.hops


@partial(jax.jit,
         static_argnames=("max_hops", "backend", "block_b"))
def _serve_eval_packed(pack, x, key, step, thresh, budget, def_thresh,
                       def_budget, per_hop_pj, transfer_pj, max_hops: int,
                       backend: str, block_b: int):
    """The packed protocol's whole decode step as ONE jitted program over
    RESIDENT span state: start-grove draw, per-lane default resolution
    (NaN-threshold / negative-budget lanes take the step's default rung
    scalars), Algorithm-2 evaluation, argmax, and affine energy pricing —
    so a dispatch uploads nothing (the step counter lives on device and
    the default knobs are cached device scalars) and downloads three
    [span] vectors instead of round-tripping rows, policy vectors and
    [span, C] logits.  Returns ``(next, hops, energy, step + 1)`` — the
    caller feeds the incremented counter straight back in, keeping the
    whole dispatch on jax's fast path with zero host->device scalar
    conversions per call."""
    from repro.core.engine import _eval_core
    start = jax.random.randint(jax.random.fold_in(key, step),
                               (x.shape[0],), 0, pack.n_groves)
    thr = jnp.where(jnp.isnan(thresh), def_thresh, thresh)
    bud = jnp.where(budget < 0, def_budget, budget)
    res = _eval_core(pack, x, start, thr, bud, max_hops, backend,
                     block_b, False, SERVE_COMPACT)
    nxt = jnp.argmax(res.proba, axis=-1).astype(jnp.int32)
    h = res.hops.astype(jnp.float32)
    energy = h * per_hop_pj + jnp.maximum(h - 1.0, 0.0) * transfer_pj
    return nxt, res.hops, energy, step + 1


class ForestReplicaServer:
    """Forest classification serving behind a :class:`DeviceDispatcher`.

    Each slot holds one pending feature row; each device hosts committed
    :class:`~repro.forest.pack.ForestPack` replicas (one per precision in
    ``precisions``, so per-request ``FogPolicy(precision=...)`` contracts
    dispatch against resident tables instead of re-packing mid-step).
    Every replica serves the whole field; the batcher's router decides
    which replica's slots a request takes, and replica ``index`` draws the
    start groves of its ``k``-th dispatch from
    ``fold_in(key(seed + index), k)``.

        server = ForestReplicaServer(gc, n_features=16)
        disp = DeviceDispatcher(server.factory, devices=serve_devices(4))
        batcher = ContinuousBatcher(128, None, server.prefill,
                                    dispatcher=disp)
        batcher.submit(Request(rid=0, prompt=x_row, max_new_tokens=1))

    ``Request.prompt`` is the feature row (float, ``[n_features]``); the
    decode "logits" are the forest's class probabilities and ``hops`` is
    the paper's per-example energy quantity, so the whole mixed-QoS /
    governor / admission-control machinery applies unchanged.

    Multi-tenant mode: pass ``registry=`` (a
    :class:`~repro.registry.ModelRegistry`) and ``cache=`` (a
    :class:`~repro.registry.PackCache`) and the replicas become
    bucket-aware — a dispatch carrying ``bucket=(tenant, version)``
    evaluates that tenant version's pack, fetched through the VMEM-
    budgeted cache (per-device committed copies, traffic-weighted
    eviction, lazy reload from artifact).  ``gc`` may then be ``None``:
    bucketless dispatches require a built-in model and raise without one.
    """

    def __init__(self, gc, n_features: int, *, backend: str = "fused",
                 precisions: Sequence[str] = ("fp32",), seed: int = 0,
                 registry=None, cache=None):
        from repro.forest.pack import ForestPack
        if (registry is None) != (cache is None):
            raise ValueError(
                "registry mode needs BOTH registry= and cache= (the cache "
                "enforces the VMEM byte budget the replicas load through)")
        self.registry = registry
        self.cache = cache
        if gc is None:
            if registry is None:
                raise ValueError(
                    "ForestReplicaServer needs a grove collection/pack, "
                    "or registry= + cache= for multi-tenant serving")
            self._packs = {}
        elif isinstance(gc, ForestPack):
            self._packs = {gc.precision: gc}
            for p in precisions:
                if p not in self._packs:
                    self._packs[p] = gc.astype(p)
        else:
            self._packs = {p: ForestPack.from_groves(gc, p)
                           for p in precisions}
        self.default_precision = tuple(precisions)[0]
        self.n_features = int(n_features)
        self.backend = backend
        self.seed = seed
        self._buffers: dict[int, np.ndarray] = {}
        self._span: int | None = None
        self._steps: dict[int, int] = {}
        self._energy_models: dict[tuple, object] = {}
        self._devices: dict[int, object] = {}
        # (precision, bucket) -> (per_hop_pj, transfer_pj) float32 scalars
        # traced into the packed dispatch (in-jit affine pricing)
        self._hop_costs: dict[tuple, tuple] = {}

    @property
    def n_groves(self) -> int:
        if not self._packs:
            raise ValueError("registry-mode server has no built-in model; "
                             "ask a bucket's pack for its grove count")
        return self._packs[self.default_precision].n_groves

    def energy_model(self, precision: str | None = None,
                     tenant: str | None = None,
                     version: int | None = None):
        """The pricing :class:`~repro.core.energy.EnergyModel` for one
        precision's packed tables (cached).  In registry mode pass
        ``tenant`` (and optionally ``version``, default live) to price
        that tenant's topology — tenants' forests need not match."""
        from repro.core.energy import EnergyModel
        precision = precision or self.default_precision
        if tenant is not None:
            if self.registry is None:
                raise ValueError("tenant-keyed energy models need a "
                                 "registry-mode server")
            if version is None:
                version = self.registry.live_version(tenant)
            key = (precision, tenant, int(version))
            m = self._energy_models.get(key)
            if m is None:
                pack = self.cache.get(tenant, version, precision)
                m = EnergyModel.from_pack(pack, self.n_features)
                self._energy_models[key] = m
            return m
        key = (precision, None, None)
        m = self._energy_models.get(key)
        if m is None:
            m = EnergyModel.from_pack(self._packs[precision],
                                      self.n_features)
            self._energy_models[key] = m
        return m

    def factory(self, index: int, device, span: int):
        """The :class:`DeviceDispatcher` ``decode_factory`` contract."""
        self._span = span
        buf = np.zeros((span, self.n_features), np.float32)
        self._buffers[index] = buf
        self._devices[index] = device
        packs = {p: jax.device_put(pack, device)
                 for p, pack in self._packs.items()}
        key = jax.device_put(jax.random.key(self.seed + index), device)
        self._steps[index] = 0
        backend = self.backend
        block_b = min(256, span)

        def decode(tokens, lengths, policy, bucket=None):
            # tokens/lengths are the slot-model plumbing; the forest serves
            # the span's feature rows.  A fresh start-grove draw per step
            # keeps the rotation-start randomization honest under
            # continuous refill.  Per-lane knobs are shaped as numpy — the
            # jit call places them beside the committed pack/x, so the
            # whole evaluation runs on THIS replica's device.
            step = self._steps[index] = self._steps[index] + 1
            thr = np.broadcast_to(
                np.asarray(policy.threshold, np.float32), (span,))
            bud = (np.broadcast_to(
                       np.asarray(policy.hop_budget, np.int32), (span,))
                   if policy.hop_budget is not None
                   else np.full((span,), NO_BUDGET, np.int32))
            prec = policy.precision or self.default_precision
            if bucket is not None:
                # registry bucket: this replica's committed copy of the
                # (tenant, version) pack at the group's precision, through
                # the VMEM-budgeted cache (lazy reload after eviction)
                if self.cache is None:
                    raise ValueError(
                        f"replica {index} got bucket {bucket!r} but the "
                        "server has no registry/cache (single-model mode)")
                tenant, version = bucket
                pack = self.cache.device_pack(tenant, version, prec,
                                              index, device)
            elif packs:
                pack = packs[prec]
            else:
                raise ValueError(
                    "registry-mode server got a bucketless dispatch; "
                    "requests must carry Request.model (no built-in "
                    "default model was constructed)")
            x = jax.device_put(buf, device)
            return _serve_eval(pack, x, key, np.int32(step),
                               thr, bud, max_hops=pack.n_groves,
                               backend=backend, block_b=block_b)

        return decode

    def _hop_cost(self, prec: str, bucket, pack):
        """Cached (per_hop_pj, transfer_pj) host floats for one pack's
        topology at one precision — the traced inputs of the in-jit affine
        energy pricing.  Host floats, not device scalars: the server is
        shared by every replica, and a scalar committed to one replica's
        device would be transferred on every other replica's dispatch
        (each replica device_puts its own copy in ``packed_factory``)."""
        key = (prec, bucket)
        c = self._hop_costs.get(key)
        if c is None:
            from repro.core.energy import EnergyModel
            m = EnergyModel.from_pack(pack, self.n_features)
            c = (float(m.per_hop_pj), float(m.transfer_pj))
            self._hop_costs[key] = c
        return c

    def packed_factory(self, index: int, device, span: int):
        """Packed-protocol replica: per-slot feature rows and policy
        vectors live as PERSISTENT device buffers, updated in place via
        donated splices when the batcher admits/retires lanes
        (:func:`~repro.core.engine.splice_slot_state`), and each dispatch
        runs
        :func:`_serve_eval_packed` — start draw, default resolution,
        evaluation, argmax and energy pricing in one launch.  ``step()``
        therefore stops paying per-step row uploads, policy re-assembly and
        logits downloads; only three [span] vectors come back per dispatch.
        The splice is compiled here, on ``device``, at every padded width a
        burst can take, so no replica compiles one while serving.
        """
        from repro.core.engine import splice_slot_state
        self._span = span
        self._devices[index] = device
        packs = {p: jax.device_put(pack, device)
                 for p, pack in self._packs.items()}
        key = jax.device_put(jax.random.key(self.seed + index), device)
        self._steps[index] = 0
        backend = self.backend
        block_b = min(256, span)
        lp = LanePolicies(span)
        # resident state; the splice path DONATES, so references live in one
        # mutable cell the closures rebind.  The per-replica step counter
        # is device-resident too: the eval returns step+1 and the closure
        # feeds it straight back — no host scalar crosses per dispatch.
        state = {
            "x": jax.device_put(
                jnp.zeros((span, self.n_features), jnp.float32), device),
            "thr": jax.device_put(jnp.asarray(lp.thresh), device),
            "bud": jax.device_put(jnp.asarray(lp.budget), device),
            "step": jax.device_put(jnp.int32(1), device),
        }
        # cached device conversions of the step's default knob scalars
        # (governor rungs form a small set; np scalars hash by value)
        knob_cache: dict[tuple, tuple] = {}
        # per-REPLICA device copies of the energy pricing scalars: a copy
        # committed to another replica's device would be re-transferred on
        # every dispatch, which dwarfs the eval enqueue itself
        hop_cache: dict[tuple, tuple] = {}
        # host mirror of the resident feature rows: the staging target for
        # admits (one vectorized write per burst), the row source for the
        # fused splice, and what prefill()-style callers (calibration) read
        mirror = np.zeros((span, self.n_features), np.float32)
        self._buffers[index] = mirror
        # compile the splice at every padded width it can take, on THIS
        # device, now: which widths a replica meets depends on where the
        # router puts each burst, and a splice compiled mid-serving stalls
        # the step that needs it (outputs discarded, inputs not donated)
        for width in {min(1 << k, span) for k in range(span.bit_length() + 1)}:
            idx = np.arange(width)
            splice_slot_state(state["x"], state["thr"], state["bud"], idx,
                              mirror[idx], lp.thresh[idx], lp.budget[idx],
                              donate=False)

        def admit_many(locals_, rows, thr, bud) -> None:
            if rows is not None:
                rows = np.asarray(rows, np.float32)
                if rows.shape[-1] != self.n_features:
                    raise ValueError(
                        f"request feature rows have {rows.shape[-1]} "
                        f"features, server expects {self.n_features}")
                mirror[locals_] = rows
            lp.stamp_many(locals_, thr, bud)

        def admit(local: int, row, thr: float, bud: int) -> None:
            admit_many(np.asarray([local]),
                       None if row is None
                       else np.asarray(row, np.float32).reshape(1, -1),
                       thr, bud)

        def retire_many(locals_) -> None:
            lp.retire_many(locals_)

        def retire(local: int) -> None:
            retire_many(np.asarray([local]))

        def _apply_staged() -> None:
            # one FUSED splice over all three buffers, driven by the knob
            # dirty set (every row admit also stamps knobs, so it covers
            # the row writes; rows come from the mirror, which is current
            # for admitted lanes and harmlessly stale for retired ones).
            # donate=False: the PREVIOUS dispatch may still be reading
            # these buffers (double-buffered pipeline) — donating would
            # stall the enqueue until it drains
            if lp.dirty:
                idx, thr, bud = lp.take_dirty()
                state["x"], state["thr"], state["bud"] = splice_slot_state(
                    state["x"], state["thr"], state["bud"],
                    idx, mirror[idx], thr, bud, donate=False)

        def enqueue(def_thresh, def_budget, precision=None, bucket=None):
            prec = precision or self.default_precision
            if bucket is not None:
                if self.cache is None:
                    raise ValueError(
                        f"replica {index} got bucket {bucket!r} but the "
                        "server has no registry/cache (single-model mode)")
                tenant, version = bucket
                pack = self.cache.device_pack(tenant, version, prec,
                                              index, device)
            elif packs:
                pack = packs[prec]
            else:
                raise ValueError(
                    "registry-mode server got a bucketless dispatch; "
                    "requests must carry Request.model (no built-in "
                    "default model was constructed)")
            hk = (prec, bucket)
            hop = hop_cache.get(hk)
            if hop is None:
                per_hop_pj, transfer_pj = self._hop_cost(prec, bucket, pack)
                hop = hop_cache[hk] = (
                    jax.device_put(jnp.float32(per_hop_pj), device),
                    jax.device_put(jnp.float32(transfer_pj), device))
            per_hop, transfer = hop
            ck = (def_thresh, def_budget)
            knobs = knob_cache.get(ck)
            if knobs is None:
                knobs = knob_cache[ck] = (
                    jax.device_put(jnp.float32(def_thresh), device),
                    jax.device_put(jnp.int32(def_budget), device))
            nxt, hops, energy, state["step"] = _serve_eval_packed(
                pack, state["x"], key, state["step"], state["thr"],
                state["bud"], knobs[0], knobs[1], per_hop, transfer,
                max_hops=pack.n_groves, backend=backend, block_b=block_b)
            return nxt, hops, energy

        def decode(def_thresh, def_budget, precision=None, bucket=None):
            _apply_staged()
            return enqueue(def_thresh, def_budget, precision, bucket)

        decode.packed = True
        # the two halves of a dispatch, which the dispatcher times apart
        decode.splice = _apply_staged
        decode.enqueue = enqueue
        # the fused kernel's walk: lane block and compaction
        decode.block_b = lane_block(block_b, span)
        decode.compact = SERVE_COMPACT
        decode.state = state            # resident device buffers (inspection)
        decode.admit = admit
        decode.admit_many = admit_many
        decode.retire = retire
        decode.retire_many = retire_many
        return decode

    def prefill(self, slot: int, prompt: np.ndarray) -> int:
        """Store the request's feature row in its slot's device buffer."""
        if self._span is None:
            raise ValueError("server not bound; construct the batcher "
                             "with its DeviceDispatcher first")
        row = np.asarray(prompt, np.float32).reshape(-1)
        if row.shape[0] != self.n_features:
            raise ValueError(
                f"request feature row has {row.shape[0]} features, "
                f"server expects {self.n_features}")
        self._buffers[slot // self._span][slot % self._span] = row
        return 1

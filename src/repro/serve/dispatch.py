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

Every replica speaks one protocol, the packed one: slot state stays with
the replica, updated by :meth:`DeviceDispatcher.admit_lanes` /
:meth:`~DeviceDispatcher.retire_lanes`, and each step the
:class:`~repro.serve.scheduler.ContinuousBatcher` calls
:meth:`DeviceDispatcher.dispatch_packed` once per serving bucket.  The
dispatcher enqueues one call per intersecting device, which returns
``(next, hops, energy)`` for its whole span WITHOUT blocking (JAX's async
dispatch lets every replica compute concurrently) and starts their copies
to the host; :meth:`~DeviceDispatcher.harvest_packed` waits once for
everything in flight and scatters each bucket's lanes into full
``[n_slots]`` host arrays.

Replication is plain device placement: :func:`replicate` ``device_put``\\ s
a pytree (e.g. a :class:`~repro.forest.pack.ForestPack`) onto each serve
device; committed inputs then pin each replica's computation to its own
device.  On CPU-only hosts (CI), force a mesh with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — see
:func:`repro.launch.mesh.serve_devices`.

Two replica kinds: :meth:`ForestReplicaServer.packed_factory`, the paper's
workload (feature rows and knobs staged on the host, uploaded as one span
when they change, one fused launch per call), and
:class:`HostReplica` (:func:`host_replica_factory`), which runs a host
``decode_fn(tokens, lengths, policy)`` — an LM decode step, a test mock —
behind the same protocol.
"""
from __future__ import annotations

import dataclasses
import inspect
import time
import types
from contextlib import nullcontext
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import (BUDGET_DEFAULT, DEAD_BUDGET, DEAD_THRESH,
                               NO_BUDGET, THRESH_DEFAULT, FogPolicy)
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
    """One in-flight packed call on one device's dispatch queue: argmax
    labels, hops and per-lane modeled pJ over the replica's whole span."""

    device: int                  # dispatcher device index
    precision: str | None        # the precision group this call serves
    lanes: np.ndarray            # global lane indices to harvest from it
    local: np.ndarray            # those lanes' offsets inside the span
    nxt: object                  # [span] int32 device array
    hops: object                 # [span] device array
    energy: object               # [span] float32 device array
    # the (model, version) registry bucket this call serves (None = the
    # single built-in model)
    bucket: tuple | None = None
    # outputs whose device-to-host copy was started at enqueue
    prefetched: int = 0
    # bytes of slot state the replica uploaded right before this call (0:
    # the call reuses the upload of an earlier call)
    uploaded: int = 0


class DeviceDispatcher:
    """Fan one continuous batch out over per-device replicas.

    decode_factory(index, device, span) -> replica
        The factory builds ONE replica of the packed protocol, placing its
        state on ``device``.  A replica has ``admit_many(local, rows, thr,
        bud)`` and ``retire_many(local)`` (staged slot updates; ``rows``
        None re-stamps knobs only), ``splice()`` (make what is staged
        visible to the next call; returns the bytes it uploaded, 0 when
        nothing changed since the last upload),
        ``enqueue(def_thresh, def_budget, precision, bucket=, local=)``
        (one call over the whole span, returning ``(next, hops, energy)``
        device arrays without blocking; ``local`` are the lanes harvested
        from it), ``block_b`` / ``compact`` (the kernel walk the
        lane-hop counters follow) and ``bucketed`` (whether it serves
        registry (model, version) buckets).  See
        :meth:`ForestReplicaServer.packed_factory` and :class:`HostReplica`.
    devices: the serve devices (default: every local device — force >1 on
        CPU via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

    The dispatcher is bound to a slot count by the batcher
    (:meth:`bind`); ``n_slots`` must divide evenly over the devices.  The
    batcher reads ``span`` to count the free slots of each replica and
    routes each refill burst to one of them; the dispatcher calls only the
    replicas whose spans a dispatch's lanes touch.

    ``phase_ns`` accumulates the host sub-phases (``PHASES``; a batcher
    shares its own dict here) and ``trace`` (set by the batcher) wraps each
    in a ``serve.dispatch.*`` / ``serve.harvest.*`` span.
    """

    def __init__(self, decode_factory: Callable, devices: Sequence | None = None):
        if devices is None:
            devices = jax.devices()
        if not devices:
            raise ValueError("DeviceDispatcher needs at least one device")
        self.devices = list(devices)
        self.decode_factory = decode_factory
        self.span: int | None = None
        self._fns: list | None = None
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

    @property
    def replicas(self) -> list:
        """The bound per-device replicas, in device order."""
        if self._fns is None:
            raise ValueError("dispatcher not bound; construct the batcher "
                             "with it first")
        return list(self._fns)

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
    def admit_lanes(self, lanes, rows, thr, bud) -> None:
        """Stage lanes' prompts and resolved policy knobs onto their
        replicas (applied at the next dispatch): one staging write per
        intersecting replica.  ``rows`` holds one prompt per lane, in lane
        order, as the requests gave it (None re-stamps the knobs only: rung
        re-stamps after a deferred-telemetry flush); ``thr`` / ``bud`` are
        ``[k]``."""
        lanes = np.asarray(lanes, np.int64)
        thr = np.asarray(thr, np.float32)
        bud = np.asarray(bud, np.int32)
        devs = lanes // self.span
        for d in np.unique(devs).tolist():
            m = devs == d
            part = rows
            if rows is not None and not m.all():     # a spilled burst
                part = list(map(rows.__getitem__, np.flatnonzero(m).tolist()))
            self._fns[d].admit_many(lanes[m] - d * self.span, part, thr[m],
                                    bud[m])

    def retire_lanes(self, lanes) -> None:
        """Stage lanes DEAD on their replicas (freed slots: a forest lane
        exits on hop 1 until re-admitted; an admit in the same step
        overrides it), one staging write per replica."""
        lanes = np.asarray(lanes, np.int64)
        devs = lanes // self.span
        for d in np.unique(devs):
            self._fns[int(d)].retire_many(
                lanes[devs == d] - int(d) * self.span)

    def dispatch_packed(self, lanes, default_thresh: float,
                        default_budget: int, precision: str | None = None,
                        bucket: tuple | None = None) -> list[Pending]:
        """Enqueue one bucket's lanes, without blocking: every intersecting
        device runs its whole span from the replica's own state — the only
        per-dispatch inputs are the step's default threshold/budget
        scalars (lanes without explicit policies resolve against them, so
        a governor rung change costs no re-splice)."""
        lanes = np.fromiter(lanes, np.int64, len(lanes)) \
            if not isinstance(lanes, np.ndarray) else lanes.astype(np.int64)
        pc, ph, tr = time.perf_counter_ns, self.phase_ns, self.trace
        out = []
        for d in np.unique(lanes // self.span):
            d = int(d)
            lo = d * self.span
            mine = lanes[(lanes >= lo) & (lanes < lo + self.span)]
            local = mine - lo
            fn = self._fns[d]
            with (span("serve.dispatch.splice", replica=d) if tr
                  else NO_SPAN):
                t0 = pc()
                uploaded = fn.splice()
                t1 = pc()
            with (span("serve.dispatch.enqueue", replica=d,
                       lanes=len(mine), precision=precision) if tr
                  else NO_SPAN):
                outs = fn.enqueue(
                    np.float32(default_thresh), np.int32(default_budget),
                    precision, bucket=bucket, local=local)
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
                        local=local, nxt=nxt, hops=hops, energy=energy,
                        bucket=bucket, prefetched=len(outs),
                        uploaded=uploaded)
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
        (``lane_hops_served``), the outputs harvested (``host_copies``),
        those whose copy was started at enqueue
        (``host_copies_prefetched``), and the slot-state uploads made for
        the calls (``span_uploads``) with their bytes
        (``span_upload_bytes``)."""
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
                if p.uploaded:
                    counts["span_uploads"] += 1
                    counts["span_upload_bytes"] += p.uploaded
        return nxt, hops, energy, pending


def _takes_bucket(fn: Callable) -> bool:
    """Does ``fn`` accept a ``bucket`` keyword ((model, version) routing
    for registry-backed multi-tenant serving)?"""
    try:
        p = inspect.signature(fn).parameters.get("bucket")
    except (TypeError, ValueError):
        return False
    return p is not None and p.kind in (p.KEYWORD_ONLY,
                                        p.POSITIONAL_OR_KEYWORD)


class HostReplica:
    """One span of the packed protocol over host callables (an LM decode
    step, a test mock): each lane's token, cache length and knobs live on
    the host.

    ``decode_fn(tokens [span], lengths [span], policy) -> (logits [span,
    V], hops [span] | None)`` gets ``policy`` with the span's resolved
    per-lane threshold / hop-budget vectors, the dispatch's precision and
    the static knobs of the ``policy`` given here; a dispatch that carries
    a (model, version) bucket passes it as ``bucket=``, which only a
    ``decode_fn`` with a ``bucket`` parameter takes (``bucketed``; the
    batcher refuses a request with a model at submit when its replicas
    are not).  ``prefill_fn(lane, prompt) -> length`` gets the GLOBAL
    lane.  ``price(hops, precision, bucket) -> pJ`` prices each lane (e.g.
    :func:`~repro.serve.governor.lane_pricer`); lanes it returns None for,
    and every lane without it, are unpriced (NaN).  A ``decode_fn`` that
    returns ``hops=None`` (a dense step) reports ``n_groves`` hops for
    every lane, and fails without ``n_groves``.

    :meth:`enqueue` does not block: the argmax and the pricing are queued
    on the replica's device behind the decode, so the replicas of a
    dispatch compute concurrently.  A lane's next input is the token its
    own bucket's call produced, and every call of one step sees the same
    inputs: a call's outputs are staged and land on the host at the next
    step's first admit, retire or call that revisits a lane.  No kernel
    walks a host replica: ``block_b`` / ``compact`` describe a dense walk
    over the span."""

    def __init__(self, decode_fn: Callable, prefill_fn: Callable, span: int,
                 *, index: int = 0, device=None, price: Callable | None = None,
                 policy: FogPolicy | None = None, n_groves: int | None = None):
        self.decode_fn = decode_fn
        self.prefill_fn = prefill_fn
        self.price = price
        self.policy = policy if policy is not None else FogPolicy()
        self.n_groves = n_groves
        self.device = device            # None: the default device
        self.lo = index * span
        self.block_b, self.compact = span, False
        self.bucketed = _takes_bucket(decode_fn)
        self.tokens = np.zeros((span,), np.int32)
        self.lengths = np.zeros((span,), np.int32)
        self.thresh = np.full((span,), THRESH_DEFAULT, np.float32)
        self.budget = np.full((span,), BUDGET_DEFAULT, np.int32)
        self._staged = np.zeros((span,), bool)
        self._outs: list = []           # (local, next device array) staged

    def _settle(self) -> None:
        """Land the last step's staged outputs as the lanes' inputs."""
        if self._outs:
            for local, nxt in self._outs:
                self.tokens[local] = np.asarray(nxt)[local]
                self.lengths[local] += 1
            self._outs.clear()
            self._staged[:] = False

    def admit_many(self, local, prompts, thr, bud) -> None:
        self._settle()
        if prompts is not None:
            for i, prompt in zip(local.tolist(), prompts):
                self.lengths[i] = self.prefill_fn(self.lo + i, prompt)
                self.tokens[i] = prompt[-1]
        self.thresh[local] = thr
        self.budget[local] = bud

    def retire_many(self, local) -> None:
        self._settle()
        self.tokens[local] = 0
        self.lengths[local] = 0
        self.thresh[local] = THRESH_DEFAULT
        self.budget[local] = BUDGET_DEFAULT

    def splice(self) -> int:
        """Nothing to upload: admits and retires write the host state."""
        return 0

    def enqueue(self, def_thresh, def_budget, precision=None, bucket=None,
                local=None):
        if self._staged[local].any():
            self._settle()
        thr = np.where(np.isnan(self.thresh), def_thresh, self.thresh)
        bud = np.where(self.budget < 0, def_budget, self.budget)
        policy = self.policy.replace(
            threshold=thr, precision=precision,
            hop_budget=None if (bud == NO_BUDGET).all() else bud)
        kw = {} if bucket is None else {"bucket": bucket}
        put = partial(jax.device_put, device=self.device)
        # copies: the call may still be reading its inputs when the next
        # step's admits rewrite the lanes
        logits, hops = self.decode_fn(put(self.tokens.copy()),
                                      put(self.lengths.copy()), policy, **kw)
        nxt = put(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        if hops is None:
            if self.n_groves is None:
                raise ValueError(
                    "decode_fn returned hops=None and the replica has no "
                    "n_groves: the serving step needs hop telemetry")
            hops = jnp.full(nxt.shape, self.n_groves, jnp.int32)
        hops = put(hops)
        energy = None if self.price is None else self.price(hops, precision,
                                                            bucket)
        energy = put(jnp.full(hops.shape, jnp.nan, jnp.float32)
                     if energy is None else energy)
        self._outs.append((local, nxt))
        self._staged[local] = True
        return nxt, hops, energy


def host_replica_factory(decode_fn: Callable, prefill_fn: Callable,
                         price: Callable | None = None, *,
                         policy: FogPolicy | None = None,
                         n_groves: int | None = None) -> Callable:
    """A :class:`DeviceDispatcher` factory whose replicas are
    :class:`HostReplica` s over the same host callables."""
    def build(index: int, device, span: int) -> HostReplica:
        return HostReplica(decode_fn, prefill_fn, span, index=index,
                           device=device, price=price, policy=policy,
                           n_groves=n_groves)
    return build


@partial(jax.jit,
         static_argnames=("max_hops", "backend", "block_b"))
def _serve_eval_packed(pack, staged, key, step, def_thresh, def_budget,
                       per_hop_pj, transfer_pj, max_hops: int,
                       backend: str, block_b: int):
    """The packed protocol's whole decode step as ONE jitted program over
    the replica's UPLOADED span state: ``staged`` is ``[span, F + 2]``
    float32, the feature rows in columns ``:F``, the lane thresholds in
    column ``F`` and the hop budgets' int32 bits in column ``F + 1``.
    Start-grove draw, per-lane default resolution (NaN-threshold /
    negative-budget lanes take the step's default rung scalars),
    Algorithm-2 evaluation, argmax, and affine energy pricing run in one
    launch, and three [span] vectors come back instead of [span, C]
    logits.  Returns ``(next, hops, energy, step + 1)`` — the step
    counter lives on the device and the caller feeds the incremented
    counter straight back in, so a call converts no host scalar."""
    from repro.core.engine import _eval_core
    n_features = staged.shape[1] - 2
    x = staged[:, :n_features]
    thresh = staged[:, n_features]
    budget = jax.lax.bitcast_convert_type(staged[:, n_features + 1],
                                          jnp.int32)
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
        disp = DeviceDispatcher(server.packed_factory,
                                devices=serve_devices(4))
        batcher = ContinuousBatcher(128, None, server.prefill,
                                    dispatcher=disp, pipeline=True)
        batcher.submit(Request(rid=0, prompt=x_row, max_new_tokens=1))

    ``Request.prompt`` is the feature row (float, ``[n_features]``); the
    "next token" is the forest's argmax class and ``hops`` is the paper's
    per-example energy quantity, so the whole mixed-QoS / governor /
    admission-control machinery applies unchanged.

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
        self._replicas: dict[int, types.SimpleNamespace] = {}
        self._span: int | None = None
        self._energy_models: dict[tuple, object] = {}
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
        """Packed-protocol replica: per-slot feature rows and policy knobs
        live in ONE host staging buffer, ``[span, F + 2]`` float32 (rows,
        then thresholds, then the hop budgets' int32 bits), which admits,
        retires and :meth:`prefill` write.  ``splice()`` uploads the whole
        staged span in one transfer when anything was written since the
        last upload, and reuses that upload otherwise (a step's second
        bucket call transfers nothing); each dispatch runs
        :func:`_serve_eval_packed` over it — start draw, default
        resolution, evaluation, argmax and energy pricing in one launch —
        and only three [span] vectors come back per dispatch.  Every
        program's shapes are fixed by ``span`` and ``F``, so ``bind``
        compiles nothing here.
        """
        self._span = span
        F = self.n_features
        packs = {p: jax.device_put(pack, device)
                 for p, pack in self._packs.items()}
        key = jax.device_put(jax.random.key(self.seed + index), device)
        backend = self.backend
        block_b = min(256, span)
        stage = np.zeros((span, F + 2), np.float32)
        rows, thresh = stage[:, :F], stage[:, F]
        budget = stage[:, F + 1].view(np.int32)
        thresh[:] = DEAD_THRESH             # every lane starts DEAD
        budget[:] = DEAD_BUDGET
        # the per-replica step counter is device-resident: the eval
        # returns step+1 and the closure feeds it straight back, so no
        # host scalar crosses per dispatch.  ``upload`` is the last upload
        # of ``stage``, None once a write made it stale
        state = {"step": jax.device_put(jnp.int32(1), device),
                 "upload": None}
        # cached device conversions of the step's default knob scalars
        # (governor rungs form a small set; np scalars hash by value)
        knob_cache: dict[tuple, tuple] = {}
        # per-REPLICA device copies of the energy pricing scalars: a copy
        # committed to another replica's device would be re-transferred on
        # every dispatch, which dwarfs the eval enqueue itself
        hop_cache: dict[tuple, tuple] = {}

        def admit_many(locals_, prompts, thr, bud) -> None:
            if prompts is not None:
                prompts = np.asarray(prompts, np.float32)
                if prompts.shape[-1] != F:
                    raise ValueError(
                        f"request feature rows have {prompts.shape[-1]} "
                        f"features, server expects {F}")
                rows[locals_] = prompts
            thresh[locals_] = thr
            budget[locals_] = bud
            state["upload"] = None

        def retire_many(locals_) -> None:
            # the rows stay: a DEAD lane exits on hop 1 whatever it holds
            thresh[locals_] = DEAD_THRESH
            budget[locals_] = DEAD_BUDGET
            state["upload"] = None

        def upload() -> int:
            if state["upload"] is not None:
                return 0
            # a copy: the next refill rewrites ``stage`` while a call may
            # still read this upload, and ``device_put`` may alias a host
            # array or read it after returning
            state["upload"] = jax.device_put(stage.copy(), device)
            return stage.nbytes

        def enqueue(def_thresh, def_budget, precision=None, bucket=None,
                    local=None):
            # every lane of the span is evaluated; ``local`` is unused
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
                pack, state["upload"], key, state["step"], knobs[0], knobs[1],
                per_hop, transfer, max_hops=pack.n_groves, backend=backend,
                block_b=block_b)
            return nxt, hops, energy

        replica = types.SimpleNamespace(
            splice=upload, enqueue=enqueue,
            admit_many=admit_many, retire_many=retire_many,
            # the fused kernel's walk: lane block and compaction
            block_b=lane_block(block_b, span), compact=SERVE_COMPACT,
            bucketed=True,              # serves registry buckets
            stage=stage,                # host staging buffer (inspection)
            state=state)                # device step counter, last upload
        self._replicas[index] = replica
        return replica

    def prefill(self, slot: int, prompt: np.ndarray) -> int:
        """Write the request's feature row into its slot's staged row
        (the batcher's ``prefill_fn`` argument; with a dispatcher the
        batcher stages rows through ``admit_lanes`` and never calls it);
        the next dispatch uploads it."""
        if self._span is None:
            raise ValueError("server not bound; construct the batcher "
                             "with its DeviceDispatcher first")
        row = np.asarray(prompt, np.float32).reshape(-1)
        if row.shape[0] != self.n_features:
            raise ValueError(
                f"request feature row has {row.shape[0]} features, "
                f"server expects {self.n_features}")
        replica = self._replicas[slot // self._span]
        replica.stage[slot % self._span, :self.n_features] = row
        replica.state["upload"] = None
        return 1

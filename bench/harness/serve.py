"""The serving cells: build the program's serving plane, warm it up, drive
it from a client's side on the host clock, and keep what the reference
and the metrics need.

The path is the one users run: ``ModelRegistry.publish`` of the field's
packs, ``ContinuousBatcher`` (packed, pipelined) -> ``ModelRegistry.route``
-> ``DeviceDispatcher.dispatch_packed`` / ``harvest_packed`` ->
``ForestReplicaServer.packed_factory`` (packs via ``PackCache``) -> the
fused kernel.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import time
from contextlib import nullcontext
from pathlib import Path

import numpy as np

pc = time.perf_counter


@dataclasses.dataclass
class Plane:
    batcher: object
    n_slots: int
    span: int
    n_chips: int


def publish(registry_dir: Path, field, group: int, tenants: list[dict],
            n_features: int):
    """Pack the field at each tenant's precision on the host CPU and
    publish it; returns the registry."""
    import jax
    import jax.numpy as jnp
    from repro.core.grove import GroveCollection
    from repro.forest.pack import ForestPack
    from repro.registry import ModelRegistry

    from harness.reference import host_device
    shutil.rmtree(registry_dir, ignore_errors=True)
    registry = ModelRegistry(registry_dir)
    feature, threshold, leaf = field
    T = feature.shape[0]
    G = T // group
    with jax.default_device(host_device()):
        gcol = GroveCollection(
            jnp.asarray(feature).reshape(G, group, -1),
            jnp.asarray(threshold).reshape(G, group, -1),
            jnp.asarray(leaf).reshape(G, group, *leaf.shape[1:]))
        for t in tenants:
            pack = ForestPack.from_groves(gcol, t["precision"])
            registry.publish(t["name"], pack,
                             extra={"n_features_in": n_features})
    return registry


def build_plane(registry, n_features: int, slots_per_chip: int, devices,
                threshold: float, server_seed: int) -> Plane:
    from repro.core.policy import FogPolicy
    from repro.registry import PackCache
    from repro.serve.dispatch import DeviceDispatcher, ForestReplicaServer
    from repro.serve.scheduler import ContinuousBatcher
    cache = PackCache(registry, budget_bytes=256 * 2**20)
    server = ForestReplicaServer(None, n_features, backend="fused",
                                 registry=registry, cache=cache,
                                 seed=server_seed)
    dispatcher = DeviceDispatcher(server.packed_factory, devices)
    n_slots = slots_per_chip * len(devices)
    batcher = ContinuousBatcher(
        n_slots, None, server.prefill, eos_id=-1,
        default_policy=FogPolicy(threshold=threshold),
        dispatcher=dispatcher, registry=registry, pipeline=True)
    return Plane(batcher, n_slots, slots_per_chip, len(devices))


class Recorder:
    """Drives ``step()`` / ``flush()`` and keeps every harvest's dispatches
    (from construction on: each replica numbers its dispatches, and the
    reference needs those numbers) with the completion count after it."""

    def __init__(self, batcher):
        self.b = batcher
        self.harvests: list = []        # (len(completed) after, [Pending])
        self._last = batcher.last_dispatches
        self.annotate = False
        self.dispatch_wall = 0.0        # s of steps that dispatched
        self.counting = False

    def _note(self):
        ld = self.b.last_dispatches
        if ld is not self._last:
            self._last = ld
            self.harvests.append((len(self.b.completed), ld))

    def step(self):
        b = self.b
        d0 = b.phase_ns["dispatch"]
        t0 = pc()
        with (_span("bench.step") if self.annotate else nullcontext()):
            b.step()
        if self.counting and b.phase_ns["dispatch"] != d0:
            self.dispatch_wall += pc() - t0
        self._note()

    def drain(self, deadline: float | None = None):
        b = self.b
        while (b.queue or b.active) and (deadline is None or pc() < deadline):
            self.step()
        if not b.queue:
            b.flush()
            self._note()


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Traffic:
    """Makes the cell's requests: row ``rows[k]`` of the test set, tenant
    ``k % n_tenants``, one label each (``max_new_tokens=1``)."""

    def __init__(self, x_test, tenants, threshold):
        from repro.core.policy import FogPolicy
        from repro.serve.scheduler import Request
        self.Request = Request
        self.x = x_test
        self.names = [t["name"] for t in tenants]
        self.pols = [FogPolicy(threshold=threshold, precision=t["precision"])
                     for t in tenants]

    def make(self, rid: int, row: int, t_submit: float):
        i = rid % len(self.names)
        return self.Request(rid=rid, prompt=self.x[row], max_new_tokens=1,
                            model=self.names[i], policy=self.pols[i],
                            t_submit=t_submit)


@dataclasses.dataclass
class Window:
    """What one measured window left behind (host side)."""
    seconds: float
    t_start: float
    t_end: float
    n_due: int = 0                  # open loop: requests due in the window
    due_abs: np.ndarray | None = None
    lag: np.ndarray | None = None   # submit - due, s
    n_submitted: int = 0
    steps: int = 0
    phase_ns: dict = dataclasses.field(default_factory=dict)
    dispatch_wall: float = 0.0
    harvest_lo: int = 0             # harvest index range of the window
    harvest_hi: int = 0
    backlog: list = dataclasses.field(default_factory=list)
    # (seconds into the window, queued + in-slot requests), every 1/8


def _phase_delta(b, before):
    return {k: b.phase_ns[k] - before[k] for k in b.phase_ns}


def run_open(plane, rec, traffic, due, rows, seconds, *, rid0=0,
             tracer=None) -> Window:
    """Open loop: submit every request when due (never waiting for
    answers), step while there is work, then follow every request due in
    the window to completion (up to a minute past the close)."""
    b = plane.batcher
    n = len(due)
    t0 = pc() + 1e-3
    due_abs = t0 + due
    lag = np.full((n,), np.nan)
    w = Window(seconds, t0, t0 + seconds, n_due=n, due_abs=due_abs, lag=lag)
    end = w.t_end
    i = 0
    if tracer:
        tracer.start()
    ph0, s0, h0 = dict(b.phase_ns), b.n_steps, len(rec.harvests)
    rec.counting = True
    make, submit = traffic.make, b.submit
    annotate = rec.annotate
    mark, every = t0, seconds / 8
    while True:
        now = pc()
        if now >= mark:
            w.backlog.append((now - t0, len(b.queue) + b.active))
            mark += every
        if now >= end:
            break
        if i < n and due_abs[i] <= now:
            j = int(np.searchsorted(due_abs, now, side="right"))
            with (_span("bench.submit") if annotate else nullcontext()):
                for k in range(i, j):
                    submit(make(rid0 + k, rows[k], due_abs[k]))
            lag[i:j] = pc() - due_abs[i:j]
            i = j
        if b.queue or b.active:
            rec.step()
        elif i < n:
            gap = due_abs[i] - pc()
            if gap > 2e-3:
                with (_span("bench.wait") if annotate else nullcontext()):
                    time.sleep(gap - 1e-3)
    rec.counting = False
    w.phase_ns = _phase_delta(b, ph0)
    w.steps = b.n_steps - s0
    w.dispatch_wall = rec.dispatch_wall
    if tracer:
        tracer.stop()
    w.harvest_lo, w.harvest_hi = h0, len(rec.harvests)
    for k in range(i, n):       # due in the window, not yet submitted
        submit(make(rid0 + k, rows[k], due_abs[k]))
        lag[k] = pc() - due_abs[k]
    w.n_submitted = n
    rec.drain(deadline=pc() + 60.0)
    return w


def run_closed(plane, rec, traffic, rows, seconds, *, backlog, rid0=0,
               tracer=None) -> Window:
    """Closed loop: keep ``backlog`` requests queued (every harvested slot
    is refilled at once) for the window, then complete what was
    submitted."""
    b = plane.batcher
    t0 = pc()
    w = Window(seconds, t0, t0 + seconds)
    end = w.t_end
    k = 0
    n_rows = len(rows)
    if tracer:
        tracer.start()
    ph0, s0, h0 = dict(b.phase_ns), b.n_steps, len(rec.harvests)
    rec.counting = True
    make, submit, q = traffic.make, b.submit, b.queue
    annotate = rec.annotate
    while True:
        now = pc()
        if now >= end:
            break
        if len(q) < backlog:
            with (_span("bench.submit") if annotate else nullcontext()):
                while len(q) < backlog:
                    submit(make(rid0 + k, rows[k % n_rows], now))
                    k += 1
        rec.step()
    rec.counting = False
    w.phase_ns = _phase_delta(b, ph0)
    w.steps = b.n_steps - s0
    w.dispatch_wall = rec.dispatch_wall
    if tracer:
        tracer.stop()
    w.harvest_lo, w.harvest_hi = h0, len(rec.harvests)
    w.n_submitted = k
    rec.drain(deadline=pc() + 60.0)
    return w


def warm_up(plane, rec, traffic, rid0, loop, *, rate=None, seconds=1.0,
            backlog=None, seed=0):
    """Compile every program the window will use, on every replica:
    admission bursts of each power-of-two width up to the span (the
    resident-state splice compiles one program per padded width; lanes fill
    lowest first, so replica ``r`` gets width ``w`` from a burst of
    ``r * span + w``), then the cell's own loop for ``seconds`` on rows
    drawn apart from the window's."""
    b = plane.batcher
    rng = np.random.default_rng(seed)
    n_rows = len(traffic.x)
    k = 0
    for r in range(plane.n_chips):
        width = 1
        while width <= plane.span:
            for _ in range(r * plane.span + width):
                b.submit(traffic.make(rid0 + k, int(rng.integers(n_rows)),
                                      pc()))
                k += 1
            rec.drain()
            width *= 2
    rid0 += k
    if loop == "open":
        due = np.sort(rng.uniform(0, seconds, int(rate * seconds)))
        run_open(plane, rec, traffic, due,
                 rng.integers(0, n_rows, len(due)), seconds, rid0=rid0)
    else:
        run_closed(plane, rec, traffic, rng.integers(0, n_rows, 1 << 16),
                   seconds, backlog=backlog, rid0=rid0)
    rec.dispatch_wall = 0.0
    gc.collect()


def served(rec, completed, window_rid_hi: int, span: int):
    """For every completed request with ``rid < window_rid_hi``: its rid,
    label, hops, completion time, and the (replica, dispatch number, lane)
    that served it.  Raises ValueError where a harvest's completions do
    not match the lanes it harvested."""
    n_all = len(completed)
    dev = np.full((n_all,), -1, np.int64)
    num = np.zeros((n_all,), np.int64)
    lane = np.zeros((n_all,), np.int64)
    count: dict[int, int] = {}
    prev = 0
    for after, pend in rec.harvests:
        lanes, devs, nums = [], [], []
        for p in pend:
            count[p.device] = count.get(p.device, 0) + 1
            lanes.append(np.asarray(p.lanes, np.int64))
            devs.append(np.full((len(p.lanes),), p.device, np.int64))
            nums.append(np.full((len(p.lanes),), count[p.device], np.int64))
        lanes_a = np.concatenate(lanes) if lanes else np.zeros(0, np.int64)
        if after - prev != len(lanes_a):
            raise ValueError(f"a harvest completed {after - prev} requests "
                             f"but harvested {len(lanes_a)} lanes")
        order = np.argsort(lanes_a, kind="stable")
        dev[prev:after] = np.concatenate(devs)[order]
        num[prev:after] = np.concatenate(nums)[order]
        lane[prev:after] = lanes_a[order] % span
        prev = after
    rid = np.fromiter((r.rid for r in completed), np.int64, n_all)
    keep = rid < window_rid_hi
    label = np.fromiter((r.generated[0] if r.generated else -1
                         for r in completed), np.int64, n_all)
    hops = np.fromiter((r.hops[0] if r.hops else -1 for r in completed),
                       np.int64, n_all)
    t_done = np.fromiter((r.t_done if r.t_done is not None else np.nan
                          for r in completed), np.float64, n_all)
    return {"rid": rid[keep], "label": label[keep], "hops": hops[keep],
            "t_done": t_done[keep], "replica": dev[keep],
            "dispatch": num[keep], "lane": lane[keep]}

"""Host instrumentation of the packed serving step: the always-on phase
counters and admission stamps, and what ``ContinuousBatcher.trace`` adds
(``serve.*`` profiler spans, collector pauses, the fused kernel's lane-hop
counters and the host-copy counters)."""
import gc
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FogPolicy, split
from repro.kernels.fused_fog import MIN_COMPACT_WIDTH, walked_lane_hops
from repro.launch.mesh import serve_devices
from repro.serve.dispatch import DeviceDispatcher, ForestReplicaServer
from repro.serve.scheduler import ContinuousBatcher, Request

SPANS = {
    "serve.step": {"step"},
    "serve.harvest": set(),
    "serve.harvest.wait": {"pending"},
    "serve.harvest.copy": {"pending"},
    "serve.bookkeep": set(),
    "serve.telemetry": set(),
    "serve.refill": {"admitted"},
    "serve.route": {"replica", "admitted", "spilled"},
    "serve.dispatch": set(),
    "serve.dispatch.splice": {"replica"},
    "serve.dispatch.enqueue": {"replica", "lanes", "precision"},
    "serve.dispatch.prefetch": {"replica"},
    "serve.gc": {"generation"},
}


def _plane(trained, n_slots):
    """A pipelined packed batcher over one device, fp32 and int8 tenants
    of one field."""
    ds, rf = trained
    server = ForestReplicaServer(split(rf, 2), ds.x_test.shape[1],
                                 backend="fused",
                                 precisions=("fp32", "int8"), seed=0)
    disp = DeviceDispatcher(server.packed_factory, serve_devices(1))
    return ContinuousBatcher(
        n_slots, None, server.prefill, eos_id=-1,
        default_policy=FogPolicy(threshold=0.7, precision="fp32"),
        dispatcher=disp, pipeline=True)


def _serve(b, x, n, steps_per_wave=2, waves=3):
    """Submit ``waves`` bursts of ``n`` requests (tenants alternate), step
    between them, drain; returns every harvest's dispatches in order."""
    harvests, last = [], b.last_dispatches
    rid = 0

    def note():
        nonlocal last
        if b.last_dispatches is not last:
            last = b.last_dispatches
            harvests.append(last)

    for _ in range(waves):
        for _ in range(n):
            b.submit(Request(rid=rid, prompt=x[rid % len(x)],
                             max_new_tokens=1,
                             policy=FogPolicy(threshold=0.7, precision=(
                                 "int8" if rid % 2 else "fp32"))))
            rid += 1
        for _ in range(steps_per_wave):
            b.step()
            note()
    while b.queue or b.active:
        b.step()
        note()
    b.flush()
    note()
    return harvests


class _Counting:
    """Stands in for ``jax.profiler.TraceAnnotation``, noting each name."""

    def __init__(self, names, real):
        self.names, self.real = names, real

    def __call__(self, name, **kw):
        self.names.append(name)
        return self.real(name, **kw)


def test_trace_off_builds_no_span(trained, monkeypatch):
    names = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        _Counting(names, jax.profiler.TraceAnnotation))
    b = _plane(trained, 8)
    _serve(b, trained[0].x_test, 12)
    assert not [n for n in names if n.startswith("serve.")]
    assert b._on_gc not in gc.callbacks
    assert b.counts == dict.fromkeys(
        ("lane_hops_walked", "lane_hops_served", "host_copies",
         "host_copies_prefetched", "routed_0", "policy_checks",
         "admit_groups", "span_uploads", "span_upload_bytes"), 0)
    assert b.phase_ns["gc"] == 0
    # the same run with tracing on does build them (the stand-in works)
    b.trace = True
    try:
        _serve(b, trained[0].x_test, 12)
    finally:
        b.trace = False
    assert {"serve.step", "serve.harvest.copy"} <= set(names)
    assert b._on_gc not in gc.callbacks


def test_trace_on_writes_every_span_inside_the_step(trained, tmp_path):
    from jax.profiler import ProfileData
    b = _plane(trained, 8)
    x = trained[0].x_test
    _serve(b, x, 12)                         # compile outside the trace
    threshold = gc.get_threshold()
    b.trace = True
    try:
        with jax.profiler.trace(str(tmp_path)):
            gc.set_threshold(20)             # collections inside the steps
            try:
                _serve(b, x, 12)
            finally:
                gc.set_threshold(*threshold)
    finally:
        b.trace = False
    path = sorted(Path(tmp_path).glob("**/*.xplane.pb"))[-1]
    events = [e for p in ProfileData.from_file(str(path)).planes
              for line in p.lines for e in line.events
              if e.name.startswith("serve.")]
    assert set(SPANS) <= {e.name for e in events}
    steps = [(e.start_ns, e.start_ns + e.duration_ns) for e in events
             if e.name == "serve.step"]

    def in_step(e):
        return any(lo <= e.start_ns and e.start_ns + e.duration_ns <= hi
                   for lo, hi in steps)

    for e in events:
        assert SPANS[e.name] <= {k for k, _ in e.stats}, e.name
        if e.name not in ("serve.step", "serve.gc"):
            assert in_step(e), e.name
    assert any(in_step(e) for e in events if e.name == "serve.gc")
    assert b.phase_ns["gc"] > 0


# the phase_ns keys before the router; each is still counted
PHASES_BEFORE = ("harvest", "bookkeep", "telemetry", "refill", "dispatch",
                 "harvest_wait", "harvest_copy", "splice", "enqueue", "gc")


def test_route_span_counter_and_phase(trained, monkeypatch):
    events = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Nesting(events))
    b = _plane(trained, 8)
    b.trace = True
    try:
        _serve(b, trained[0].x_test, 12)
    finally:
        b.trace = False
    assert set(PHASES_BEFORE) < set(b.phase_ns)
    assert 0 < b.phase_ns["route"] < b.phase_ns["refill"]
    # one replica: every admitted request is routed to it
    assert b.counts["routed_0"] == len(b.completed) == 36
    # each route opens and closes inside a refill
    stack, inside = [], []
    for what, name in events:
        if what == "enter":
            if name == "serve.route":
                inside.append(stack[-1])
            stack.append(name)
        else:
            assert stack.pop() == name
    assert inside and set(inside) == {"serve.refill"}


class _Nesting:
    """Stands in for ``jax.profiler.TraceAnnotation``, noting each
    ``serve.*`` span's entry and exit in order."""

    def __init__(self, events):
        self.events = events

    def __call__(self, name, **kw):
        events = self.events

        class _Span:
            def __enter__(self):
                events.append(("enter", name))

            def __exit__(self, *exc):
                events.append(("exit", name))

        return _Span()


@pytest.mark.parametrize("trace", [False, True])
def test_sub_phases_fit_inside_their_phase(trained, monkeypatch, trace):
    events = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Nesting(events))
    b = _plane(trained, 8)
    b.trace = trace
    try:
        _serve(b, trained[0].x_test, 12)
    finally:
        b.trace = False
    ph = b.phase_ns
    assert all(ph[k] > 0 for k in ("harvest_wait", "harvest_copy", "splice",
                                   "enqueue"))
    assert ph["harvest_wait"] + ph["harvest_copy"] <= ph["harvest"]
    assert ph["splice"] + ph["enqueue"] <= ph["dispatch"]
    names = [n for _, n in events if n.startswith("serve.")]
    if not trace:
        assert not names
        return
    # each prefetch opens and closes inside an enqueue, one per enqueue
    stack, inside = [], []
    for what, name in events:
        if what == "enter":
            if name == "serve.dispatch.prefetch":
                inside.append(stack[-1])
            stack.append(name)
        else:
            assert stack.pop() == name
    assert not stack
    assert inside == ["serve.dispatch.enqueue"] * events.count(
        ("enter", "serve.dispatch.enqueue")) != []


def _mock_decode(n_slots):
    def decode_fn(tokens, lengths, policy):
        logits = np.zeros((n_slots, 4), np.float32)
        logits[:, 2] = 1.0
        return jnp.asarray(logits), jnp.ones((n_slots,), jnp.int32)
    return decode_fn


@pytest.mark.parametrize("path", ["packed", "host"])
def test_admission_stamp_lies_between_submit_and_done(trained, path):
    if path == "packed":
        b = _plane(trained, 8)
        _serve(b, trained[0].x_test, 12)
    else:
        b = ContinuousBatcher(4, _mock_decode(4), lambda slot, p: len(p),
                              eos_id=-1)
        for rid in range(10):
            b.submit(Request(rid=rid, prompt=np.asarray([2]),
                             max_new_tokens=2))
        b.run()
    assert len(b.completed) > len(b.slots)   # some requests queued
    for r in b.completed:
        assert r.t_submit <= r.t_admit <= r.t_done



@pytest.mark.parametrize("trace", [False, True])
def test_policy_check_and_admit_group_counters(trained, trace):
    """Two tenants sharing one policy object each: with ``trace`` on,
    ``policy_checks`` reads 2 (one validation per distinct policy) and
    ``admit_groups`` one per tenant present in each refill burst, at most
    2 a burst; with it off both stay 0."""
    b = _plane(trained, 8)
    x = trained[0].x_test
    pols = [FogPolicy(threshold=0.7, precision=p) for p in ("fp32", "int8")]
    tenants_per_burst = []
    admit = b.dispatcher.admit_lanes

    def noting(lanes, rows, thr, bud):
        if rows is not None:
            tenants_per_burst.append(
                len({b.slots[i].request.rid % 2 for i in lanes}))
        return admit(lanes, rows, thr, bud)

    b.dispatcher.admit_lanes = noting
    b.trace = trace
    try:
        rid = 0
        for wave in (12, 3, 7):
            for _ in range(wave):
                b.submit(Request(rid=rid, prompt=x[rid % len(x)],
                                 max_new_tokens=1, policy=pols[rid % 2]))
                rid += 1
            b.step()
        while b.queue or b.active:
            b.step()
        b.flush()
    finally:
        b.trace = False
    assert len(b.completed) == 22
    assert len(tenants_per_burst) >= 3
    if not trace:
        assert b.counts["policy_checks"] == b.counts["admit_groups"] == 0
        return
    assert b.counts["policy_checks"] == 2
    assert b.counts["admit_groups"] == sum(tenants_per_burst)
    assert b.counts["admit_groups"] <= 2 * len(tenants_per_burst)


def test_span_upload_counters(trained):
    """With ``trace`` on, ``span_uploads`` counts one upload a step that
    dispatched (every step admits or retires on the one replica), never
    more than the calls made, and ``span_upload_bytes`` the whole staged
    span each time; the upload stays inside the ``splice`` sub-phase."""
    b = _plane(trained, 8)
    F = trained[0].x_test.shape[1]
    b.trace = True
    try:
        harvests = _serve(b, trained[0].x_test, 12)
    finally:
        b.trace = False
    c = b.counts
    calls = sum(len(pend) for pend in harvests)
    assert len(harvests) <= c["span_uploads"] <= calls
    assert c["span_uploads"] == len(harvests)
    assert calls > len(harvests)        # the second tenant's calls reused it
    assert c["span_upload_bytes"] == c["span_uploads"] * 8 * (F + 2) * 4
    assert b.phase_ns["splice"] > 0


def _recount_walked(h, block):
    """The fused kernel's compacted walk replayed hop by hop: a block loops
    while any lane is live, and each hop walks the narrowest power-of-two
    prefix (of at least one lane row) holding the live lanes."""
    h = np.concatenate([h, np.zeros((-len(h)) % block, h.dtype)])
    walked = 0
    for lo in range(0, len(h), block):
        blk = h[lo:lo + block]
        j = 0
        while (blk > j).any():
            live = int((blk > j).sum())
            w = block
            while w % 2 == 0 and w // 2 >= max(live, MIN_COMPACT_WIDTH):
                w //= 2
            walked += w
            j += 1
    return walked


@pytest.mark.parametrize("n_slots", [128, 512])
def test_lane_hop_counters_match_a_recount(trained, n_slots):
    b = _plane(trained, n_slots)
    block = b.dispatcher.replicas[0].block_b
    assert block == min(256, n_slots)
    b.trace = True
    try:
        harvests = _serve(b, trained[0].x_test, n_slots // 2 + 3)
    finally:
        b.trace = False
    walked = served = 0
    for pend in harvests:
        assert len({p.precision for p in pend}) == len(pend)
        for p in pend:
            h = np.asarray(p.hops)
            walked += _recount_walked(h, block)
            served += int(h[p.local].sum())
    assert (b.counts["lane_hops_walked"],
            b.counts["lane_hops_served"]) == (walked, served)
    assert served == sum(r.hops[0] for r in b.completed)
    assert 0 < served < walked


def test_walked_lane_hops_by_hand():
    h = np.array([3] * 10 + [1] * 246)
    # compacted: the whole block, then the one-row prefix twice
    assert walked_lane_hops(h, 256, True) == 256 + 128 + 128
    assert walked_lane_hops(h, 256, False) == 3 * 256
    # a ragged batch is dead-lane padded to whole blocks
    assert walked_lane_hops(np.ones(300, np.int64), 256, False) == 512
    assert walked_lane_hops(np.zeros(4, np.int64), 128, True) == 0

"""Pipelined-vs-synchronous equivalence of the packed serving hot loop.

The double-buffered pipeline (``pipeline=True``) overlaps host bookkeeping
for step t+1 with the device computing step t, and ``telemetry_every=k``
defers the governor/ledger/stats replay to flush boundaries.  Neither is
allowed to change WHAT is served: for a fixed seed and a fixed submission
schedule, every mode must produce identical labels, hops, shed sets,
governor transitions and registry version pinning — the pipeline moves
work in wall time, never in step time.

Everything runs in ONE process: ``make_dataset`` is process-seeded, so
cross-process runs see different data, but within a process each mode
rebuilds an identical plane from the same seed.
"""
import heapq
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FogPolicy, split
from repro.core.engine import splice_lanes, splice_slot_state
from repro.core.energy import EnergyModel
from repro.core.policy import (BUDGET_DEFAULT, DEAD_BUDGET, DEAD_THRESH,
                               NO_BUDGET, LanePolicies, THRESH_DEFAULT,
                               lane_knobs)
from repro.forest import ForestPack
from repro.launch.mesh import serve_devices
from repro.registry import ModelRegistry, PackCache
from repro.serve.dispatch import DeviceDispatcher, ForestReplicaServer
from repro.serve.governor import (EnergyGovernor, TenantLedger,
                                  default_ladder)
from repro.serve.scheduler import ContinuousBatcher, Request


# --------------------------------------------------------------------------
# splice primitives
# --------------------------------------------------------------------------

def test_splice_copy_matches_donating_and_preserves_source():
    """donate=False must compute the same buffer as donate=True while
    leaving the source readable (the pipeline's previous dispatch may
    still hold it)."""
    base = np.arange(24, dtype=np.float32).reshape(8, 3)
    idx = [1, 4, 6]
    vals = -np.ones((3, 3), np.float32)
    donated = splice_lanes(jnp.asarray(base), idx, vals, donate=True)
    src = jnp.asarray(base)
    copied = splice_lanes(src, idx, vals, donate=False)
    np.testing.assert_array_equal(np.asarray(donated), np.asarray(copied))
    # the copying splice left its source untouched and alive
    np.testing.assert_array_equal(np.asarray(src), base)
    want = base.copy()
    want[idx] = -1.0
    np.testing.assert_array_equal(np.asarray(copied), want)


def test_splice_slot_state_matches_three_single_splices():
    """The fused three-buffer splice is exactly three splice_lanes calls
    sharing one index set (any burst width, pow-2 padding included)."""
    rng = np.random.default_rng(0)
    n, f = 16, 5
    x = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
    thr = jnp.asarray(rng.random(n).astype(np.float32))
    bud = jnp.asarray(rng.integers(0, 9, n).astype(np.int32))
    for width in (1, 3, 8, 16):
        idx = np.sort(rng.choice(n, size=width, replace=False))
        rows = rng.normal(size=(width, f)).astype(np.float32)
        t = rng.random(width).astype(np.float32)
        b = rng.integers(0, 9, width).astype(np.int32)
        fx, fthr, fbud = splice_slot_state(x, thr, bud, idx, rows, t, b,
                                           donate=False)
        np.testing.assert_array_equal(
            np.asarray(fx),
            np.asarray(splice_lanes(x, idx, rows, donate=False)))
        np.testing.assert_array_equal(
            np.asarray(fthr),
            np.asarray(splice_lanes(thr, idx, t, donate=False)))
        np.testing.assert_array_equal(
            np.asarray(fbud),
            np.asarray(splice_lanes(bud, idx, b, donate=False)))


def test_lane_policies_dirty_tracking_round_trip():
    lp = LanePolicies(6)
    assert not lp.dirty
    lp.stamp_many(np.asarray([4, 1]), np.float32(0.5), np.int32(3))
    lp.retire_many(np.asarray([2]))
    assert lp.dirty
    idx, thr, bud = lp.take_dirty()
    np.testing.assert_array_equal(idx, [1, 2, 4])   # ascending, clears
    np.testing.assert_array_equal(thr, np.float32([0.5, DEAD_THRESH, 0.5]))
    np.testing.assert_array_equal(bud, np.int32([3, DEAD_BUDGET, 3]))
    assert not lp.dirty
    # sentinels resolve against the step default, concrete stamps win
    lp.stamp(0, THRESH_DEFAULT, BUDGET_DEFAULT)
    rthr, rbud = lp.resolve(FogPolicy(threshold=0.9, hop_budget=7))
    assert rthr[0] == np.float32(0.9) and rbud[0] == 7
    assert rthr[1] == np.float32(0.5) and rbud[1] == 3


# --------------------------------------------------------------------------
# the packed replica against a plain replay
# --------------------------------------------------------------------------

_REPLAY = r"""
import json, sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import split
from repro.core.energy import EnergyModel
from repro.core.engine import _eval_core
from repro.core.policy import (BUDGET_DEFAULT, DEAD_BUDGET, DEAD_THRESH,
                               NO_BUDGET, THRESH_DEFAULT)
from repro.data import make_dataset
from repro.forest import ForestPack, TrainConfig, train_random_forest
from repro.launch.mesh import serve_devices
from repro.serve.dispatch import DeviceDispatcher, ForestReplicaServer

n_dev, SPAN, SEED = int(sys.argv[1]), 8, 5
N = SPAN * n_dev
ds = make_dataset("penbased")
rf = train_random_forest(ds.x_train, ds.y_train, ds.n_classes,
                         TrainConfig(n_trees=8, max_depth=5, seed=1))
gc = split(rf, 2)
F = ds.x_test.shape[1]
PRECS = ("fp32", "int8")
server = ForestReplicaServer(gc, F, backend="fused", precisions=PRECS,
                             seed=SEED)
disp = DeviceDispatcher(server.packed_factory, serve_devices(n_dev))
disp.bind(N)

# the replay's state: rows and knobs set on the host, one draw counter a
# replica, one increment per call
x = np.zeros((N, F), np.float32)
thr = np.full((N,), DEAD_THRESH, np.float32)
bud = np.full((N,), DEAD_BUDGET, np.int32)
step = [1] * n_dev
packs = {p: ForestPack.from_groves(gc, p) for p in PRECS}
G = packs["fp32"].n_groves


def replay(lanes, def_thr, def_bud, prec, spans):
    pack = packs[prec]
    m = EnergyModel.from_pack(pack, F)
    out = {}
    for d in np.unique(lanes // SPAN).tolist():
        lo = d * SPAN
        start = jax.random.randint(
            jax.random.fold_in(jax.random.key(SEED + d), step[d]),
            (SPAN,), 0, G)
        step[d] += 1
        t = thr[lo:lo + SPAN]
        b = bud[lo:lo + SPAN]
        t = np.where(np.isnan(t), np.float32(def_thr), t)
        b = np.where(b < 0, np.int32(def_bud), b)
        res = _eval_core(pack, jnp.asarray(x[lo:lo + SPAN]), start,
                         jnp.asarray(t), jnp.asarray(b), G, "fused",
                         SPAN, False, True)
        nxt = np.asarray(jnp.argmax(res.proba, axis=-1)).astype(np.int32)
        h = np.asarray(res.hops)
        spans[f"{d}/{prec}"] = h.tolist()
        hf = h.astype(np.float32)
        e = (hf * np.float32(m.per_hop_pj)
             + np.maximum(hf - np.float32(1), np.float32(0))
             * np.float32(m.transfer_pj))
        for i in lanes[(lanes >= lo) & (lanes < lo + SPAN)].tolist():
            out[i] = (int(nxt[i - lo]), int(h[i - lo]),
                      float(e[i - lo]))
    return out


rng = np.random.default_rng(11)
live = {}                       # lane -> precision
served, replayed = [], []
served_spans, replayed_spans = [], []
spilled = False
for t in range(7):
    # retires: about a third of the live lanes
    gone = [i for i in sorted(live) if rng.random() < 0.35]
    if gone:
        disp.retire_lanes(gone)
        thr[gone], bud[gone] = DEAD_THRESH, DEAD_BUDGET
        for i in gone:
            del live[i]
    # an admission burst into free lanes, over every replica it finds
    free = [i for i in range(N) if i not in live]
    k = 0 if t == 4 else min(len(free), int(rng.integers(2, SPAN + 3)))
    lanes = np.sort(rng.choice(free, size=k, replace=False))
    spilled |= len(np.unique(lanes // SPAN)) > 1
    rows = ds.x_test[rng.integers(0, len(ds.x_test), k)]
    t_k = np.where(rng.random(k) < 0.5, THRESH_DEFAULT,
                   rng.uniform(0.3, 0.95, k)).astype(np.float32)
    b_k = np.where(rng.random(k) < 0.5, BUDGET_DEFAULT,
                   np.where(rng.random(k) < 0.5, NO_BUDGET,
                            rng.integers(1, G + 1, k))).astype(np.int32)
    disp.admit_lanes(lanes, list(rows), t_k, b_k)
    x[lanes], thr[lanes], bud[lanes] = rows, t_k, b_k
    for i in lanes.tolist():
        live[i] = PRECS[int(rng.integers(2))]
    # a knob restamp of some live lanes (rows None)
    if t % 3 == 2:
        re = np.asarray(sorted(live)[::2], np.int64)
        disp.admit_lanes(re, None, np.full(len(re), 0.6, np.float32),
                         np.full(len(re), 2, np.int32))
        thr[re], bud[re] = 0.6, 2
    # the fleet's rung moves after step 3
    def_thr, def_bud = (0.8, NO_BUDGET) if t < 3 else (0.5, 3)
    want, spans = {}, {}
    for prec in PRECS:          # fp32 and int8 buckets in the same step
        lanes = np.asarray([i for i in sorted(live) if live[i] == prec],
                           np.int64)
        if lanes.size:
            disp.dispatch_packed(lanes, def_thr, def_bud, precision=prec)
            want.update(replay(lanes, def_thr, def_bud, prec, spans))
    nxt, hops, energy, pend = disp.harvest_packed(N)
    # every lane of each call's span, the retired ones included
    served_spans.append({f"{p.device}/{p.precision}":
                         np.asarray(p.hops).tolist() for p in pend})
    replayed_spans.append(spans)
    served.append({i: (int(nxt[i]), int(hops[i]), float(energy[i]))
                   for i in want})
    replayed.append(want)
print(json.dumps({"served": [sorted(s.items()) for s in served],
                  "replayed": [sorted(r.items()) for r in replayed],
                  "spans": [served_spans, replayed_spans],
                  "spilled": spilled}))
"""


@pytest.mark.parametrize("n_dev", [1, 4])
def test_packed_replica_serves_as_a_plain_replay(n_dev):
    """A seeded sequence of admits, retires (one step retires and admits
    nothing), knob restamps, a fleet rung change and fp32 + int8 bucket
    calls in one step (and, on four forced host devices, bursts spilled
    over replicas) served through the packed replica gives, bit for bit,
    the (label, hops, energy) of a plain replay that keeps rows and knobs
    on the host and evaluates each call's span with the replica's start
    draw, one counter step per call; the hops of every lane of each
    call's span, retired lanes included, match too."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}",
               PYTHONPATH=str(Path(__file__).resolve().parent.parent / "src"))
    proc = subprocess.run([sys.executable, "-c", _REPLAY, str(n_dev)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["served"] == out["replayed"]
    assert out["spans"][0] == out["spans"][1]
    assert sum(len(s) for s in out["served"]) > 20
    assert out["spilled"] == (n_dev > 1)


# --------------------------------------------------------------------------
# closed-loop mode equivalence
# --------------------------------------------------------------------------

N_SLOTS = 8


def _run_mode(trained, *, pipeline, telemetry_every, governor_budget="none",
              max_queue=None, waves=6, wave_n=16, steps_per_wave=3):
    """One full serving run at a fixed submission schedule: ``waves``
    bursts of ``wave_n`` requests with ``steps_per_wave`` steps between
    bursts, then drain.  Fresh plane + governor per call, same seed."""
    ds, rf = trained
    gc = split(rf, 2)
    server = ForestReplicaServer(gc, ds.x_test.shape[1], backend="fused",
                                 precisions=("fp32", "int8"), seed=0)
    disp = DeviceDispatcher(server.packed_factory, serve_devices(1))
    base = FogPolicy(threshold=0.7, precision="fp32")
    gov = None
    if governor_budget != "none":
        model = server.energy_model("fp32")
        ladder = default_ladder(base, model, governor_budget)
        gov = EnergyGovernor(ladder, governor_budget, model=model,
                             window=16, patience=2, cooldown=10_000,
                             warmup=4)
    b = ContinuousBatcher(N_SLOTS, None, server.prefill, eos_id=-1,
                          default_policy=base, governor=gov,
                          dispatcher=disp, max_queue=max_queue,
                          pipeline=pipeline,
                          telemetry_every=telemetry_every)
    rid = 0
    for _ in range(waves):
        for _ in range(wave_n):
            pol = (FogPolicy(threshold=0.55, precision="int8")
                   if rid % 3 == 0 else None)
            b.submit(Request(rid=rid, prompt=ds.x_test[rid % len(ds.x_test)],
                             max_new_tokens=1, policy=pol,
                             tier="bulk" if rid % 3 == 0 else "std"))
            rid += 1
        for _ in range(steps_per_wave):
            b.step()
    while b.active or b.queue:
        b.step()
    b.flush()
    return b, gov


def _served(b):
    return {r.rid: (tuple(r.generated), tuple(r.hops))
            for r in b.completed}


def test_pipelined_step_is_bit_equal_to_synchronous(trained):
    """pipeline=True with per-step telemetry serves exactly what the
    synchronous step serves: same labels, same hops, same shed set, same
    fleet stats — under queue pressure and a mixed-precision bucket mix."""
    sync, _ = _run_mode(trained, pipeline=False, telemetry_every=1,
                        max_queue=24)
    pipe, _ = _run_mode(trained, pipeline=True, telemetry_every=1,
                        max_queue=24)
    assert _served(sync) == _served(pipe)
    assert ({r.rid for r in sync.shed_requests}
            == {r.rid for r in pipe.shed_requests})
    for attr in ("total_hops", "n_events", "n_offered", "n_shed"):
        assert getattr(sync.stats, attr) == getattr(pipe.stats, attr)
    assert sync.stats.tier_summary() == pipe.stats.tier_summary()


def test_pipelined_governor_transitions_match_synchronous(trained):
    """A TIGHT energy SLO walks the ladder mid-run; the pipeline (which
    harvests one step late) must reproduce the synchronous governor's
    transition sequence and final rung exactly — telemetry is replayed by
    harvest index, not by wall order."""
    ds, rf = trained
    gc = split(rf, 2)
    server = ForestReplicaServer(gc, ds.x_test.shape[1], backend="fused",
                                 precisions=("fp32", "int8"), seed=0)
    model = server.energy_model("fp32")
    # budget around the cost of ~1.5 hops: the base rung breaches, the
    # ladder walks — both modes must agree on every step of that walk
    budget = float(np.asarray(model.lane_pj(np.asarray([2]))[0])) * 1e-3 * 0.8
    sync, gov_s = _run_mode(trained, pipeline=False, telemetry_every=1,
                            governor_budget=budget)
    pipe, gov_p = _run_mode(trained, pipeline=True, telemetry_every=1,
                            governor_budget=budget)
    assert _served(sync) == _served(pipe)
    assert gov_s.transitions == gov_p.transitions
    assert len(gov_s.transitions) >= 1      # the SLO actually bit
    assert gov_s.rung == gov_p.rung
    assert gov_s.rolling_nj == pytest.approx(gov_p.rolling_nj)


def test_deferred_telemetry_changes_when_not_what(trained):
    """telemetry_every=8 batches the replay but, with a metering-only
    governor (no stepping), must leave every post-flush observable equal
    to the per-step account: labels, stats totals, rolling estimate."""
    ref, gov_r = _run_mode(trained, pipeline=False, telemetry_every=1,
                           governor_budget=None)
    defer, gov_d = _run_mode(trained, pipeline=True, telemetry_every=8,
                             governor_budget=None)
    assert _served(ref) == _served(defer)
    assert ref.stats.total_hops == defer.stats.total_hops
    assert ref.stats.n_events == defer.stats.n_events
    assert ref.stats.total_pj == pytest.approx(defer.stats.total_pj)
    assert gov_r.rolling_nj == pytest.approx(gov_d.rolling_nj)
    assert gov_r.transitions == gov_d.transitions == []


def _run_swap(trained, tmp_path, *, pipeline, telemetry_every):
    """Registry-mode serving with a mid-run hot-swap at a fixed step
    boundary: 2 full steps on v1 traffic, publish v2, second burst,
    drain.  Version pinning happens at slot assignment, so both modes
    must pin the same rid -> version map."""
    ds, rf = trained
    pack = ForestPack.from_groves(split(rf, 2))
    reg = ModelRegistry(tmp_path / f"reg-{pipeline}-{telemetry_every}")
    reg.publish("t", pack)
    cache = PackCache(reg, budget_bytes=4 * pack.table_bytes)
    server = ForestReplicaServer(None, ds.x_test.shape[1], backend="fused",
                                 registry=reg, cache=cache, seed=0)
    disp = DeviceDispatcher(server.packed_factory, serve_devices(1))
    b = ContinuousBatcher(4, None, server.prefill, eos_id=-1,
                          default_policy=FogPolicy(threshold=0.7,
                                                   precision="fp32"),
                          dispatcher=disp, registry=reg, pipeline=pipeline,
                          telemetry_every=telemetry_every)
    for rid in range(8):
        b.submit(Request(rid=rid, prompt=ds.x_test[rid], max_new_tokens=1,
                         model="t"))
    for _ in range(2):
        b.step()
    reg.publish("t", pack)                  # hot-swap mid-flight
    for rid in range(8, 16):
        b.submit(Request(rid=rid, prompt=ds.x_test[rid], max_new_tokens=1,
                         model="t"))
    while b.active or b.queue:
        b.step()
    b.flush()
    return {r.rid: (r.version, tuple(r.generated), tuple(r.hops))
            for r in b.completed}


def test_hot_swap_version_pinning_matches_across_modes(trained, tmp_path):
    sync = _run_swap(trained, tmp_path, pipeline=False, telemetry_every=1)
    pipe = _run_swap(trained, tmp_path, pipeline=True, telemetry_every=4)
    assert sync == pipe
    assert len(sync) == 16
    versions = {v for v, _, _ in sync.values()}
    assert versions == {1, 2}               # the swap actually happened
    # requests in flight (or queued) before the publish stayed on v1
    assert all(sync[rid][0] == 1 for rid in range(8))
    assert all(sync[rid][0] == 2 for rid in range(8, 16))


def test_flush_is_idempotent_and_drains_inflight(trained):
    """flush() mid-run harvests the in-flight dispatch and replays the
    buffered telemetry; a second flush is a no-op."""
    ds, rf = trained
    gc = split(rf, 2)
    server = ForestReplicaServer(gc, ds.x_test.shape[1], backend="fused",
                                 precisions=("fp32",), seed=0)
    disp = DeviceDispatcher(server.packed_factory, serve_devices(1))
    b = ContinuousBatcher(4, None, server.prefill, eos_id=-1,
                          default_policy=FogPolicy(threshold=0.7,
                                                   precision="fp32"),
                          dispatcher=disp, pipeline=True, telemetry_every=16)
    for rid in range(4):
        b.submit(Request(rid=rid, prompt=ds.x_test[rid], max_new_tokens=1))
    b.step()                                # dispatched, nothing harvested
    assert len(b.completed) == 0
    b.flush()
    assert len(b.completed) == 4            # in-flight drained
    assert b.stats.n_events == 4            # telemetry replayed
    before = (b.stats.n_events, b.stats.total_hops, len(b.completed))
    b.flush()
    assert (b.stats.n_events, b.stats.total_hops, len(b.completed)) == before


# --------------------------------------------------------------------------
# admission groups against the per-request rule
# --------------------------------------------------------------------------

def _old_update(st, hops, e, tiers):
    """``ServeStats.update`` as it was: the per-tier fold one event at a
    time."""
    st.total_hops += int(hops.sum())
    st.n_events += int(hops.size)
    priced = np.isfinite(e)
    st.total_pj += float(e[priced].sum())
    st.n_priced += int(priced.sum())
    st.has_energy = st.has_energy or bool(priced.any())
    for i, tier in enumerate(tiers):
        t = st._tier(tier)
        t["n_events"] += 1
        if priced[i]:
            t["total_pj"] += float(e[i])
            t["n_priced"] += 1


class _PerRequest(ContinuousBatcher):
    """The packed path's admission, retirement and telemetry as they were
    before admission groups: one Python pass per request, a free-slot heap
    per replica, a lane set per serving bucket (a copy of the old rule, the
    oracle the grouped path is held to)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.heaps = [list(range(lo, lo + self._span))
                      for lo in range(0, self._n_slots, self._span)]
        self.last = len(self.heaps) - 1
        self.key = [None] * self._n_slots
        self.buckets = {}

    def _take(self):
        q, heaps = self.queue, self.heaps
        n = len(heaps)
        best, most = 0, -1
        for k in range(1, n + 1):
            d = (self.last + k) % n
            if len(heaps[d]) > most:
                best, most = d, len(heaps[d])
        for d in [(best + k) % n for k in range(n)]:
            n0 = len(q)
            while q and heaps[d]:
                yield heapq.heappop(heaps[d]), q.popleft()
            if n0 > len(q):
                self.last = d
            if not q:
                return

    def _refill(self):
        if not self.queue or self._n_active == self._n_slots:
            return
        lanes, rows, thrs, buds = [], [], [], []
        now = time.perf_counter()
        for i, req in self._take():
            req.t_admit = now
            if (req.model is not None and req.version is None
                    and self.registry is not None):
                req.version = self.registry.route(req.model, req.rid)
            self._lane_req[i] = req
            self._occ_mask[i] = True
            pol = req.policy
            if pol is None:
                pol = self._tenant_rung(req.tenant)
            thr, bud = lane_knobs(pol)
            lanes.append(i)
            rows.append(req.prompt)
            thrs.append(thr)
            buds.append(bud)
            key = (req.model, req.version,
                   None if pol is None else pol.precision)
            self.key[i] = key
            self.buckets.setdefault(key, set()).add(i)
        self._n_active += len(lanes)
        self.dispatcher.admit_lanes(np.asarray(lanes, np.int64),
                                    np.asarray(rows, np.float32), thrs, buds)

    def _dispatch(self):
        default = (self.governor.current if self.governor is not None
                   else self.default_policy)
        def_thr = float(np.asarray(default.threshold))
        def_bud = (int(np.asarray(default.hop_budget))
                   if default.hop_budget is not None else NO_BUDGET)
        for key in list(self.buckets):
            lanes = self.buckets[key]
            if not lanes:
                del self.buckets[key]
                continue
            model, version, prec = key
            self.dispatcher.dispatch_packed(
                lanes, def_thr, def_bud,
                precision=prec if prec is not None else default.precision,
                bucket=None if model is None else (model, version))
        self._inflight = True
        self._inflight_occ = np.flatnonzero(self._occ_mask)

    def _process_harvest(self):
        nxt, hops, energy, pend = self.dispatcher.harvest_packed(
            self._n_slots)
        self.last_dispatches = pend
        self._inflight = False
        occ = self._inflight_occ
        reqs, retired = [], []
        now = time.perf_counter()
        for i in occ.tolist():
            req = self._lane_req[i]
            reqs.append(req)
            req.generated.append(int(nxt[i]))
            req.hops.append(int(hops[i]))
            if (int(nxt[i]) == self.eos_id
                    or len(req.generated) >= req.max_new_tokens):
                req.done = True
                req.t_done = now
                self.stats.note_done(req.tier)
                self.completed.append(req)
                self._lane_req[i] = None
                self._occ_mask[i] = False
                self._n_active -= 1
                heapq.heappush(self.heaps[i // self._span], i)
                self.buckets[self.key[i]].discard(i)
                self.key[i] = None
                retired.append(i)
        if retired:
            self.dispatcher.retire_lanes(retired)
        if occ.size:
            self._tel_buf.append((hops[occ], energy[occ], reqs, occ))
            self._steps_since_flush += 1
            if self._steps_since_flush >= self.telemetry_every:
                self._flush_telemetry()

    def _flush_telemetry(self):
        buf, self._tel_buf = self._tel_buf, []
        self._steps_since_flush = 0
        ledger = self.ledger
        trans = None
        if ledger is not None:
            govs = [g for _, g in ledger.items()]
            trans = [(g, len(g.transitions)) for g in govs]
        fleet = []
        for hops, energy, reqs, lanes in buf:
            tiers = [r.tier for r in reqs]
            devices = self.dispatcher.lane_devices(lanes)
            e = energy
            if self.governor is not None:
                fleet.append((e, devices))
            elif ledger is not None:
                e = energy.copy()
                by_tenant = {}
                for i, r in enumerate(reqs):
                    by_tenant.setdefault(r.tenant, []).append(i)
                for tenant, idxs in by_tenant.items():
                    gov = ledger.governor_for(tenant)
                    if gov is None:
                        e[idxs] = np.nan
                        continue
                    gov.ingest([(e[idxs], devices[idxs])])
            _old_update(self.stats, hops, e, tiers)
            if self.registry is not None:
                by_version = {}
                for i, r in enumerate(reqs):
                    if r.model is not None and r.version is not None:
                        by_version.setdefault((r.model, r.version),
                                              []).append(i)
                for (tenant, version), idxs in by_version.items():
                    _old_update(self.registry.stats_for(tenant, version),
                                hops[idxs], e[idxs], [tiers[i] for i in idxs])
        if self.governor is not None and fleet:
            self.governor.ingest(fleet)
        if trans is not None and any(len(g.transitions) != n
                                     for g, n in trans):
            lanes, thrs, buds = [], [], []
            for i in np.flatnonzero(self._occ_mask).tolist():
                req = self._lane_req[i]
                rung = (None if req.policy is not None
                        else self._tenant_rung(req.tenant))
                if rung is None:
                    continue
                thr, bud = lane_knobs(rung)
                lanes.append(i)
                thrs.append(thr)
                buds.append(bud)
                key = (req.model, req.version, rung.precision)
                if key != self.key[i]:
                    self.buckets[self.key[i]].discard(i)
                    self.key[i] = key
                    self.buckets.setdefault(key, set()).add(i)
            if lanes:
                self.dispatcher.admit_lanes(lanes, None, thrs, buds)


CASES = ("two_tenants", "canary", "governor", "ledger", "four_replicas")


def _plane_for(case, trained, tmp_path, cls, pipeline):
    """A fresh packed plane for one case (same seeds every call) and the
    request maker of its traffic."""
    ds, rf = trained
    gc = split(rf, 2)
    F = ds.x_test.shape[1]
    base = FogPolicy(threshold=0.7, precision="fp32")
    devices = serve_devices(1)
    reg = gov = None
    if case in ("two_tenants", "canary", "ledger"):
        reg = ModelRegistry(tmp_path / f"reg-{cls.__name__}-{pipeline}")
        for name, prec in (("a", "fp32"), ("b", "int8"), ("c", "fp32")):
            reg.publish(name, ForestPack.from_groves(gc, prec))
        if case == "canary":
            reg.publish("a", ForestPack.from_groves(gc, "fp32"), canary=0.5)
        server = ForestReplicaServer(
            None, F, backend="fused", registry=reg, seed=0,
            cache=PackCache(reg, budget_bytes=64 * 2**20))
    else:
        server = ForestReplicaServer(gc, F, backend="fused",
                                     precisions=("fp32", "int8"), seed=0)
        if case == "four_replicas":
            devices = devices * 4
    model = EnergyModel.from_pack(ForestPack.from_groves(gc, "fp32"), F)
    # about 1.6 hops a request: the base rung breaches and the ladder walks
    tight = float(np.asarray(model.lane_pj(np.asarray([2]))[0])) * 1e-3 * 0.8

    def mk_gov(budget):
        return EnergyGovernor(default_ladder(base, model, budget), budget,
                              model=model, window=16, patience=2,
                              cooldown=10_000, warmup=4)

    if case == "governor":
        gov = mk_gov(tight)
    elif case == "ledger":
        # tenant a walks its ladder mid-run (its lanes are restamped), b
        # holds, c is billed by no governor (NaN energy)
        gov = TenantLedger()
        gov.add("a", mk_gov(tight))
        gov.add("b", mk_gov(tight * 100))
    n_slots = 32 if case == "four_replicas" else N_SLOTS
    b = cls(n_slots, None, server.prefill, eos_id=-1, default_policy=base,
            governor=gov, dispatcher=DeviceDispatcher(
                server.packed_factory, devices),
            registry=reg, pipeline=pipeline,
            telemetry_every=1 if gov is not None else 3)
    pols = {"fp32": FogPolicy(threshold=0.55, precision="fp32"),
            "int8": FogPolicy(threshold=0.6, precision="int8")}

    def make(rid):
        tier = ("gold", "std", "bulk")[rid % 3]
        x = ds.x_test[rid % len(ds.x_test)]
        if reg is None:
            pol = (pols["int8"] if rid % 3 == 0 else None)
            return Request(rid=rid, prompt=x, max_new_tokens=1 + rid % 2,
                           policy=pol, tier=tier)
        tenant = ("a", "b", "c")[rid % 3] if case == "ledger" else (
            "a" if case == "canary" else ("a", "b")[rid % 2])
        pol = None
        if case != "ledger" or tenant == "c":
            pol = pols["int8" if tenant == "b" else "fp32"]
        return Request(rid=rid, prompt=x, max_new_tokens=1 + rid % 2,
                       policy=pol, model=tenant, tier=tier)

    return b, make, reg, gov


def _serve_recorded(case, trained, tmp_path, cls, pipeline):
    """Serve ``case``'s schedule and record every admission burst, knob
    restamp and dispatch the plane saw, and each harvest's dispatches."""
    b, make, reg, gov = _plane_for(case, trained, tmp_path, cls, pipeline)
    disp = b.dispatcher
    seen = {"bursts": [], "restamps": [], "dispatches": [], "harvests": []}
    admit, dispatch = disp.admit_lanes, disp.dispatch_packed

    def admit_noting(lanes, rows, thr, bud):
        lanes = [int(i) for i in lanes]
        # the default sentinel (NaN) written as a label, so it compares
        knobs = [("default" if t != t else t, u) for t, u in zip(
            np.asarray(thr, np.float32).tolist(),
            np.asarray(bud, np.int32).tolist())]
        if rows is None:
            seen["restamps"].append(sorted(zip(lanes, knobs)))
        else:
            seen["bursts"].append([(b.slots[i].request.rid, i, k)
                                   for i, k in zip(lanes, knobs)])
        return admit(lanes, rows, thr, bud)

    def dispatch_noting(lanes, *a, **kw):
        seen["dispatches"].append((sorted(int(i) for i in lanes), a,
                                   sorted(kw.items())))
        return dispatch(lanes, *a, **kw)

    disp.admit_lanes, disp.dispatch_packed = admit_noting, dispatch_noting
    last = b.last_dispatches

    def step():
        nonlocal last
        n0 = len(b.completed)
        b.step()
        if b.last_dispatches is not last:
            last = b.last_dispatches
            seen["harvests"].append((n0, len(b.completed), last))

    wave = 12 if case == "four_replicas" else 7
    rid = 0
    for w in range(8):
        for _ in range(wave + w % 3):
            b.submit(make(rid))
            rid += 1
        for _ in range(1 + w % 2):
            step()
    while b.queue or b.active:
        step()
    n0 = len(b.completed)
    b.flush()
    if b.last_dispatches is not last:
        seen["harvests"].append((n0, len(b.completed), b.last_dispatches))
    return b, seen, reg, gov


def _stats(st):
    return ((st.total_hops, st.n_events, st.n_priced, st.n_offered,
             st.n_shed, st.has_energy),
            {k: (t["n_done"], t["n_shed"], t["n_events"], t["n_priced"])
             for k, t in st.tiers.items()},
            st.total_pj, {k: t["total_pj"] for k, t in st.tiers.items()})


def _assert_stats_match(new, old):
    counts_n, tiers_n, pj_n, tier_pj_n = _stats(new)
    counts_o, tiers_o, pj_o, tier_pj_o = _stats(old)
    assert counts_n == counts_o
    assert tiers_n == tiers_o
    assert pj_n == pytest.approx(pj_o, rel=1e-12)
    assert tier_pj_n.keys() == tier_pj_o.keys()
    for k in tier_pj_n:
        assert tier_pj_n[k] == pytest.approx(tier_pj_o[k], rel=1e-12)


@pytest.mark.parametrize("case", CASES)
def test_admission_groups_serve_as_the_per_request_rule(trained, tmp_path,
                                                        case):
    """The grouped packed path, pipelined, against the per-request rule it
    replaced, under one seed and schedule: the same lanes and knobs for
    every request in the same bursts, one admission stamp per burst, the
    same dispatches in the same order (so the same start groves), the
    same (generated, hops) and versions, completions appended in ascending
    lane order per harvest, and the same telemetry (energy to 1e-12) in
    the fleet stats, the registry's per-version stats and the governors;
    the synchronous grouped path ends with the same telemetry."""
    new, seen, reg, gov = _serve_recorded(case, trained, tmp_path,
                                          ContinuousBatcher, True)
    old, seen_old, reg_old, gov_old = _serve_recorded(
        case, trained, tmp_path, _PerRequest, True)
    sync, _, reg_sync, _ = _serve_recorded(case, trained, tmp_path,
                                           ContinuousBatcher, False)
    for key in ("bursts", "restamps", "dispatches"):
        assert seen[key] == seen_old[key], key
    by_rid = {r.rid: r for r in new.completed}
    for burst in seen["bursts"]:
        assert len({by_rid[rid].t_admit for rid, _, _ in burst}) == 1
    assert ([(r.rid, r.version, r.generated, r.hops) for r in new.completed]
            == [(r.rid, r.version, r.generated, r.hops)
                for r in old.completed])
    assert len(new.completed) == new.stats.n_offered
    lane_of = {rid: i for burst in seen["bursts"] for rid, i, _ in burst}
    for lo, hi, pend in seen["harvests"]:
        done = [lane_of[r.rid] for r in new.completed[lo:hi]]
        assert done == sorted(done)
        assert set(done) <= {int(i) for p in pend for i in p.lanes}
    _assert_stats_match(new.stats, old.stats)
    _assert_stats_match(new.stats, sync.stats)
    assert new.stats.tier_summary() == sync.stats.tier_summary()
    if reg is not None:
        for key in {(r.model, r.version) for r in new.completed}:
            _assert_stats_match(reg.stats_for(*key), reg_old.stats_for(*key))
            _assert_stats_match(reg.stats_for(*key),
                                reg_sync.stats_for(*key))
            assert (reg.stats_for(*key).tier_summary()
                    == reg_sync.stats_for(*key).tier_summary())
        assert all(r.version == reg.route(r.model, r.rid)
                   for r in new.completed)
    if case in ("governor", "ledger"):
        pairs = ([(gov, gov_old)] if case == "governor" else
                 [(gov.governor_for(t), gov_old.governor_for(t))
                  for t in ("a", "b")])
        for g, g_old in pairs:
            assert g.transitions == g_old.transitions
            assert g.rung == g_old.rung
            assert g.rolling_nj == pytest.approx(g_old.rolling_nj,
                                                 rel=1e-12)
    # the case's mechanism ran
    if case == "governor":
        assert gov.transitions
    elif case == "ledger":
        assert gov.governor_for("a").transitions and seen["restamps"]
        assert new.stats.n_priced < new.stats.n_events    # tenant c: NaN
    elif case == "canary":
        assert {r.version for r in new.completed} == {1, 2}
    elif case == "four_replicas":
        assert any(len({i // 8 for _, i, _ in burst}) > 1
                   for burst in seen["bursts"])
    else:
        assert any(len({p.precision for p in pend}) == 2
                   for _, _, pend in seen["harvests"])

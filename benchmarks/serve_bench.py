"""Closed-loop load harness for the data-parallel serving plane.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m benchmarks.serve_bench --smoke

Drives the REAL serving stack — ForestReplicaServer replicas behind a
DeviceDispatcher behind a ContinuousBatcher, every dispatch a real fused-
kernel evaluation with real per-request hop/energy telemetry — under a
Poisson open-arrival workload with mixed QoS tiers, warmup + measurement
windows, and admission control, and emits ``BENCH_serve.json`` with one row
per (n_devices, precision, governor) config: throughput (req/s), p50/p99
latency, mean nJ/request, shed rate.

The serving hot loop under test is the packed path: slot feature rows and
per-lane policy knobs are staged on the host and uploaded as one span per
replica when they change, each dispatch is ONE jitted program per device
returning packed ``(next, hops, energy)`` (argmax + pricing in-jit, no
logits download), and the batcher runs ``pipeline=True`` — step t's
dispatch is harvested at the start of step t+1 so host bookkeeping for
t+1 overlaps device compute of t.  Telemetry is buffered and replayed
every ``telemetry_every`` steps (exact under ``flush()``).

Concurrency accounting (the "virtual clock").  CI and this container run on
a single CPU core, so N virtual XLA host devices execute their dispatches
sequentially in wall time — wall-clock alone cannot show data-parallel
speedup anywhere except on real multi-core/multi-chip hardware.  Following
the profiling-and-modeling methodology the ISSUE cites (arXiv 1902.11119),
the harness therefore runs everything for real but *accounts* device
concurrency: a calibration phase measures each precision's per-dispatch
service time ``s`` sequentially, and each step's virtual duration is

    vstep = max(wall_step - sum_over_dispatches(s), 0) + max_over_devices(busy_d)

i.e. the measured non-overlappable time (Python scheduling, policy
assembly, harvest — everything that is NOT device compute) plus the
longest single device's compute, which is what a concurrent fleet would
wait for.  On one device ``max_d busy_d == sum s`` and the virtual clock
EQUALS wall time — single-device rows are the built-in sanity check (see
the ``wall_rps`` column).  Both clocks are reported; the virtual-speedup
gate reads the virtual one.

Wall-clock scaling gate.  Wall time additionally carries its own gate: the
``wall_baseline`` row serves the SAME per-device batch (``span`` lanes) on
one device that each of the 4-dev row's devices serves, so comparing their
``wall_rps`` asks "does adding devices at fixed per-device batch keep the
host out of the way?"  On this 1-core container device compute is
timeshared, so the honest expectation is ratio ~1.0x (the target on real
multi-core hardware is >= 1.5x); the gate enforces the >= 1.0x floor —
the pre-refactor host-bound loop scored 0.89x.  Ambient container load
swings single-shot wall measurements by up to 2x, so every row repeats
its measured window ``WALL_REPS`` times and reports the best (noise is
one-sided: interference only ever slows a run down).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

OUT_PATH = Path(__file__).resolve().parent.parent / "BENCH_serve.json"

# QoS mix: fraction of arrivals per tier.  "gold" buys accuracy with a
# HIGHER exit threshold — in FoG a higher MaxDiff gate means more groves
# vote (same compiled program, per-lane knob); "bulk" trades accuracy for
# energy with a lower threshold AND int8 tables (its own precision group);
# "contract" (governor rows only, carved out of "std") carries a hard
# per-request energy_budget_nj.
TIERS = (("std", 0.70), ("gold", 0.20), ("bulk", 0.10))
CONTRACT_FRAC = 0.20
BASE_THRESH = 0.7     # std tier / calibration
GOLD_THRESH = 1.0     # premium: nearly every grove votes
BULK_THRESH = 0.4     # bulk: exit early, and on int8 tables

SPAN = 256        # wall-baseline per-device batch (lanes per device)
TEL_EVERY = 8     # deferred-telemetry flush cadence (steps)
WALL_REPS = 3     # measured-window repeats; wall_rps = best of

SMOKE_GRID = [
    dict(n_devices=1, precision="fp32", governor=False),
    dict(n_devices=4, precision="fp32", governor=False),
    dict(n_devices=1, precision="int8", governor=False),
    dict(n_devices=4, precision="int8", governor=False),
    dict(n_devices=4, precision="fp32", governor=True),
]
FULL_GRID = [
    dict(n_devices=d, precision=p, governor=g)
    for p in ("fp32", "bf16", "int8")
    for d in (1, 4)
    for g in (False, True)
]
# span-matched single-device row for the wall-clock scaling gate: serves
# the same 256-lane per-device batch the 4-dev rows serve per device
WALL_BASELINE = dict(n_devices=1, precision="fp32", governor=False,
                     wall_baseline=True)


def _percentile(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs, float), q)) if xs else 0.0


class _Plane:
    """One (n_devices, n_slots)-keyed serving plane, shared across the grid
    rows so each (span, precision) program compiles exactly once.  Built on
    the packed replica protocol; per-precision service
    times are calibrated LAZILY — a row pays only for the precisions its
    traffic mix can actually dispatch (``ensure_svc``)."""

    def __init__(self, gc, ds, n_devices, n_slots, precisions, backend,
                 seed=0):
        from repro.launch.mesh import serve_devices
        from repro.serve.dispatch import DeviceDispatcher, ForestReplicaServer

        self.ds = ds
        self.n_slots = n_slots
        self.precisions = tuple(precisions)
        self.server = ForestReplicaServer(
            gc, ds.x_test.shape[1], backend=backend, precisions=precisions,
            seed=seed)
        self.dispatcher = DeviceDispatcher(self.server.packed_factory,
                                           serve_devices(n_devices))
        self.dispatcher.bind(n_slots)
        self._warm_full_path(precisions)
        self.svc: dict[str, float] = {}

    def _warm_full_path(self, precisions):
        """Drain one throwaway batcher burst through the REAL pipelined
        step path (admits, span uploads, per-precision dispatch, harvest,
        deferred telemetry flush) so the first timed capacity probe pays zero
        first-step costs — every replica's program compiles here."""
        from repro.core.policy import FogPolicy
        from repro.serve.scheduler import ContinuousBatcher, Request
        b = ContinuousBatcher(self.n_slots, None, self.server.prefill,
                              eos_id=-1,
                              default_policy=FogPolicy(threshold=BASE_THRESH,
                                                       precision=precisions[0]),
                              dispatcher=self.dispatcher,
                              pipeline=True, telemetry_every=TEL_EVERY)
        alt = [FogPolicy(threshold=BULK_THRESH, precision=p)
               for p in precisions[1:]]
        for rid in range(2 * self.n_slots):
            pol = alt[rid % len(alt)] if alt and rid % 3 == 0 else None
            b.submit(Request(rid=rid,
                             prompt=self.ds.x_test[rid % len(self.ds.x_test)],
                             max_new_tokens=1, policy=pol))
        while b.active or b.queue:
            b.step()
        b.flush()

    def ensure_svc(self, prec: str) -> None:
        """Calibrate one precision's sequential per-dispatch service time
        on demand: admit real feature rows onto device 0's span, warm the
        (already compiled) program, best-of-5 a single dispatch+harvest,
        then retire the lanes.  The span upload happens on the warmup
        dispatches, so the timed number is pure steady-state device
        compute — exactly what the virtual clock must not double-count."""
        if prec in self.svc:
            return
        import numpy as np
        from repro.core.policy import NO_BUDGET
        span = self.dispatcher.span
        lanes = np.arange(span, dtype=np.int64)
        rows = np.resize(self.ds.x_test.astype(np.float32),
                         (span, self.ds.x_test.shape[1]))
        self.dispatcher.admit_lanes(
            lanes, rows, np.full((span,), BASE_THRESH, np.float32),
            np.full((span,), NO_BUDGET, np.int32))
        for _ in range(2):
            self.dispatcher.dispatch_packed(lanes, BASE_THRESH, NO_BUDGET,
                                            precision=prec)
            self.dispatcher.harvest_packed(self.n_slots)
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            self.dispatcher.dispatch_packed(lanes, BASE_THRESH, NO_BUDGET,
                                            precision=prec)
            self.dispatcher.harvest_packed(self.n_slots)
            best = min(best, time.perf_counter() - t0)
        self.svc[prec] = best
        self.dispatcher.retire_lanes(lanes)


def _make_governor(plane, base_policy, budget_nj):
    from repro.serve.governor import EnergyGovernor, default_ladder
    model = plane.server.energy_model("fp32")
    ladder = default_ladder(base_policy, model, budget_nj)
    return EnergyGovernor(ladder, budget_nj, model=model, window=64,
                          patience=2)


def _run_row(plane, cfg, n_requests, warmup_frac, seed, arrival_factor):
    """One grid row: capacity probe, then the Poisson closed loop
    (repeated WALL_REPS times; metrics from the best-virtual repeat,
    wall_rps from the best wall repeat — ambient load only ever slows a
    repeat down)."""
    import numpy as np
    from repro.core.policy import FogPolicy
    from repro.serve.scheduler import ContinuousBatcher, Request

    ds = plane.ds
    n_slots = plane.n_slots
    row_prec = cfg["precision"]
    base = FogPolicy(threshold=BASE_THRESH, precision=row_prec)
    rng = np.random.default_rng(seed)

    # every precision this row's traffic mix can dispatch: its own base
    # precision, plus int8 (the bulk tier always rides along, and the
    # governor ladder's lower rungs drop to int8)
    needed = sorted({row_prec, "int8"})
    for p in needed:
        plane.ensure_svc(p)

    def svc_of(pending):
        return plane.svc.get(pending.precision or row_prec,
                             plane.svc[row_prec])

    def new_batcher(governor=None, max_queue=None):
        return ContinuousBatcher(
            n_slots, None, plane.server.prefill, eos_id=-1,
            default_policy=base, governor=governor,
            dispatcher=plane.dispatcher, max_queue=max_queue,
            shed_policy="reject", pipeline=True,
            telemetry_every=TEL_EVERY)

    def vclock_step(b):
        t0 = time.perf_counter()
        b.step()
        wall = time.perf_counter() - t0
        busy: dict[int, float] = {}
        total = 0.0
        # pipelined loop: last_dispatches is the set HARVESTED this step
        # (issued one step earlier) — every dispatch is credited exactly
        # once over the run, just one step late.  The very first step has
        # harvested nothing, so its device term is 0 (NOT wall — that
        # would double-count the host time already in the first term).
        for p in b.last_dispatches:
            s = svc_of(p)
            busy[p.device] = busy.get(p.device, 0.0) + s
            total += s
        vstep = max(wall - total, 0.0) + (max(busy.values()) if busy
                                          else 0.0)
        return vstep, wall

    # -- capacity probe: saturated burst, no arrivals process ------------
    cap_n = 4 * n_slots
    b = new_batcher()
    for rid in range(cap_n):
        b.submit(Request(rid=rid, prompt=ds.x_test[rid % len(ds.x_test)],
                         max_new_tokens=1))
    vtot = wtot = 0.0
    while len(b.completed) < cap_n:
        v, w = vclock_step(b)
        vtot += v
        wtot += w
    b.flush()
    capacity_rps = cap_n / vtot
    arrival_rps = arrival_factor * capacity_rps

    # -- closed-loop workload (shared across repeats) --------------------
    budget_nj = None
    if cfg["governor"]:
        # price the capacity burst to size the SLO: slightly under the
        # measured mean forces the governor to actually govern
        model0 = plane.server.energy_model(row_prec)
        burst_hops = np.asarray([r.hops[0] for r in b.completed])
        mean_nj = float(np.asarray(model0.lane_pj(burst_hops)).mean()) * 1e-3
        budget_nj = 0.9 * mean_nj
    inter = rng.exponential(1.0 / arrival_rps, size=n_requests)
    arrivals = np.cumsum(inter)
    tiers = rng.choice([t for t, _ in TIERS], size=n_requests,
                       p=[f for _, f in TIERS])
    contract_mask = (cfg["governor"]
                     & (tiers == "std")
                     & (rng.random(n_requests) < CONTRACT_FRAC
                        / TIERS[0][1]))
    contract_factor = rng.choice([1.3, 2.0], size=n_requests)
    warmup_n = int(warmup_frac * n_requests)

    def run_loop(governor):
        contract_budgets = {}

        def make_request(rid):
            tier = tiers[rid]
            kw = {}
            if contract_mask[rid]:
                nj = float(contract_factor[rid]) * budget_nj
                contract_budgets[rid] = nj
                kw["energy_budget_nj"] = nj
            elif tier == "gold":
                kw["policy"] = FogPolicy(threshold=GOLD_THRESH)
            elif tier == "bulk":
                kw["policy"] = FogPolicy(threshold=BULK_THRESH,
                                         precision="int8")
            return Request(rid=rid, prompt=ds.x_test[rid % len(ds.x_test)],
                           max_new_tokens=1, **kw)

        b = new_batcher(governor=governor, max_queue=n_slots)
        vnow = 0.0
        wall_total = 0.0
        next_rid = 0
        arrival_vtime = np.full((n_requests,), np.nan)
        done_vtime = np.full((n_requests,), np.nan)
        n_done_seen = 0
        v_measure_start = None
        w_measure_start = None
        shed_rids = set()
        guard = 0
        while len(b.completed) + len(b.shed_requests) < n_requests:
            guard += 1
            if guard > 500_000:
                raise RuntimeError("serve_bench closed loop did not drain")
            while next_rid < n_requests and arrivals[next_rid] <= vnow:
                rid = next_rid
                if rid == warmup_n:
                    v_measure_start, w_measure_start = vnow, wall_total
                arrival_vtime[rid] = vnow
                if not b.submit(make_request(rid)):
                    shed_rids.add(rid)
                next_rid += 1
            if b.active == 0 and not b.queue:
                if next_rid < n_requests:  # idle: jump to the next arrival
                    vnow = max(vnow, float(arrivals[next_rid]))
                    continue
                break
            v, w = vclock_step(b)
            vnow += v
            wall_total += w
            for r in b.completed[n_done_seen:]:
                done_vtime[r.rid] = vnow
            n_done_seen = len(b.completed)
        b.flush()
        return dict(
            b=b, governor=governor, vnow=vnow, wall_total=wall_total,
            arrival_vtime=arrival_vtime, done_vtime=done_vtime,
            shed_rids=shed_rids, contract_budgets=contract_budgets,
            v_measure_start=v_measure_start,
            w_measure_start=w_measure_start)

    reps = []
    for _ in range(WALL_REPS):
        governor = (_make_governor(plane, base, budget_nj)
                    if cfg["governor"] else None)
        reps.append(run_loop(governor))

    def w_window(rep):
        return rep["wall_total"] - (rep["w_measure_start"] or 0.0)

    def wall_rps_of(rep):
        done = sum(1 for r in rep["b"].completed if r.rid >= warmup_n)
        return done / max(w_window(rep), 1e-9)

    def v_rps_of(rep):
        done = sum(1 for r in rep["b"].completed if r.rid >= warmup_n)
        window = rep["vnow"] - (rep["v_measure_start"] or 0.0)
        return done / max(window, 1e-9)

    wall_runs = [wall_rps_of(rep) for rep in reps]
    v_runs = [v_rps_of(rep) for rep in reps]
    # metrics come from the best-virtual rep (and wall_rps is best-of-reps
    # below): the runner timeshares all virtual devices on one core and
    # ambient load swings any single window ~2x, so a fixed rep would gate
    # on scheduler noise, not on the serving plane
    r0 = reps[int(np.argmax(v_runs))]
    b, governor = r0["b"], r0["governor"]
    contract_budgets = r0["contract_budgets"]

    # -- metrics over the best rep's measurement window ------------------
    measured = [r for r in b.completed if r.rid >= warmup_n]
    lat_ms = [(r0["done_vtime"][r.rid] - r0["arrival_vtime"][r.rid]) * 1e3
              for r in measured]
    v_window = r0["vnow"] - (r0["v_measure_start"] or 0.0)
    offered_m = n_requests - warmup_n
    shed_m = sum(1 for rid in r0["shed_rids"] if rid >= warmup_n)

    def price(req):
        prec = (req.policy.precision if req.policy is not None
                and req.policy.precision is not None else row_prec)
        model = (governor.model_for(prec) if governor is not None
                 else plane.server.energy_model(prec))
        return float(np.asarray(model.lane_pj(
            np.asarray(req.hops))).sum()) * 1e-3

    nj = [price(r) for r in measured]
    contracts_offered = [r for r in b.completed if r.rid in contract_budgets]
    contracts_held = [r for r in contracts_offered
                      if price(r) <= contract_budgets[r.rid] + 1e-9]

    span = plane.dispatcher.span
    wall_rps = max(wall_runs)
    steps = max(b.n_steps, 1)
    row = dict(
        n_devices=cfg["n_devices"], precision=row_prec,
        governor=bool(cfg["governor"]), n_slots=n_slots, span=span,
        pipeline=True, telemetry_every=TEL_EVERY,
        n_requests=n_requests, warmup_n=warmup_n,
        capacity_rps=round(capacity_rps, 1),
        arrival_rps=round(arrival_rps, 1),
        throughput_rps=round(max(v_runs), 1),
        throughput_rps_runs=[round(x, 1) for x in v_runs],
        wall_rps=round(wall_rps, 1),
        wall_rps_runs=[round(x, 1) for x in wall_runs],
        wall_over_capacity=round(wall_rps / max(capacity_rps, 1e-9), 3),
        p50_ms=round(_percentile(lat_ms, 50), 3),
        p99_ms=round(_percentile(lat_ms, 99), 3),
        mean_nj_per_req=round(float(np.mean(nj)) if nj else 0.0, 4),
        mean_hops=round(float(np.mean([r.hops[0] for r in measured]))
                        if measured else 0.0, 3),
        completed=len(measured), offered=offered_m, shed=shed_m,
        shed_rate=round(shed_m / max(1, offered_m), 4),
        svc_us={p: round(plane.svc[p] * 1e6, 1) for p in needed},
        svc_measured=needed,
        host_phase_us_per_step={k: round(v / 1e3 / steps, 1)
                                for k, v in b.phase_ns.items()},
        contracts=dict(offered=len(contracts_offered),
                       held=len(contracts_held)),
    )
    if cfg.get("wall_baseline"):
        row["wall_baseline"] = True
    if governor is not None:
        row["governor_budget_nj"] = round(budget_nj, 4)
        row["governor_rung_final"] = governor.rung
        row["governor_transitions"] = len(governor.transitions)
        row["device_nj"] = {str(d): round(v, 4)
                            for d, v in sorted(governor.device_nj.items())}
    return row


def bench(smoke: bool, seed: int = 0) -> dict:
    import numpy as np  # noqa: F401 (ensures numpy before jax init)
    from benchmarks.common import forest_for
    from repro.core.grove import split
    from repro.data import make_dataset

    grid = list(SMOKE_GRID if smoke else FULL_GRID) + [dict(WALL_BASELINE)]
    n_requests = 6144 if smoke else 12288
    # fixed-slot rows: 1024 slots per step so per-dispatch device COMPUTE
    # dominates the fixed per-dispatch runtime cost even at span =
    # n_slots/4 — these carry the virtual-speedup gate.  The wall_baseline
    # row instead serves SPAN slots on one device (span-matched with the
    # 4-dev rows' per-device batch) and carries the wall-clock floor gate.
    n_slots_fixed = 1024
    precisions = (("fp32", "int8") if smoke
                  else ("fp32", "bf16", "int8"))

    ds = make_dataset("penbased")
    gc = split(forest_for("penbased"), 2)

    planes: dict[tuple, _Plane] = {}
    rows = []
    for cfg in grid:
        d = cfg["n_devices"]
        n_slots = (SPAN * d if cfg.get("wall_baseline") else n_slots_fixed)
        if (d, n_slots) not in planes:
            planes[d, n_slots] = _Plane(gc, ds, d, n_slots, precisions,
                                        backend="fused", seed=seed)
        t0 = time.time()
        row = _run_row(planes[d, n_slots], cfg, n_requests, warmup_frac=0.2,
                       seed=seed, arrival_factor=1.3)
        row["row_seconds"] = round(time.time() - t0, 1)
        tag = " [wall-baseline]" if cfg.get("wall_baseline") else ""
        print(f"[serve_bench] {row['n_devices']}dev {row['precision']} "
              f"gov={row['governor']}{tag}: {row['throughput_rps']} req/s "
              f"(wall {row['wall_rps']}), p50 {row['p50_ms']}ms "
              f"p99 {row['p99_ms']}ms, {row['mean_nj_per_req']} nJ/req, "
              f"shed {100 * row['shed_rate']:.1f}%", flush=True)
        rows.append(row)

    import jax
    return dict(
        dataset="penbased", topology="8x2", backend="fused",
        smoke=smoke, seed=seed,
        host_devices=len(jax.devices()),
        methodology=(
            "packed device-resident dispatch (argmax + energy pricing "
            "in-jit), pipelined batcher (harvest t-1 overlaps dispatch t), "
            "deferred telemetry flushed every "
            f"{TEL_EVERY} steps; device concurrency accounted in virtual "
            "time: vstep = max(wall - sum(svc), 0) + max_device(busy); "
            "svc calibrated lazily per served precision; single-device "
            "rows have virtual == wall by construction; wall_rps is the "
            f"best of {WALL_REPS} measured-window repeats (ambient load "
            "is one-sided noise); the wall_baseline row is span-matched "
            "to the 4-dev rows for the wall floor gate"),
        rows=rows,
    )


# --------------------------------------------------------------------------
# gate
# --------------------------------------------------------------------------

def serve_gate(data: dict, min_speedup: float = 1.5,
               wall_floor: float = 1.0,
               wall_target: float = 1.5) -> list[str]:
    """CI gate over BENCH_serve.json: multi-device virtual throughput must
    beat single-device by ``min_speedup`` per matched precision (governor
    off), 4-dev wall-clock throughput must not fall below the span-matched
    single-device baseline (``wall_floor``; ``wall_target`` is the real-
    hardware goal and is reported, not enforced, on this 1-core runner),
    every completed per-request energy contract must have held, and the
    overloaded closed loop must actually have shed."""
    fails = []
    rows = data.get("rows", [])
    if not rows:
        return ["no rows in BENCH_serve.json"]
    by = {(r["n_devices"], r["precision"], r["governor"],
           r.get("n_slots")): r for r in rows}
    for r in rows:
        if r["governor"] or r["n_devices"] < 4 or r.get("wall_baseline"):
            continue
        single = by.get((1, r["precision"], False, r.get("n_slots")))
        if single is None:
            continue
        ratio = r["throughput_rps"] / max(single["throughput_rps"], 1e-9)
        if ratio < min_speedup:
            fails.append(
                f"{r['precision']}: {r['n_devices']}-device throughput "
                f"{r['throughput_rps']} req/s is only {ratio:.2f}x the "
                f"single-device {single['throughput_rps']} req/s "
                f"(need >= {min_speedup}x)")
    # wall-clock floor: 4-dev wall_rps vs the span-matched 1-dev baseline
    baselines = [r for r in rows if r.get("wall_baseline")]
    if not baselines:
        fails.append("no wall_baseline row: the wall-clock scaling floor "
                     "was never measured")
    for base in baselines:
        four = next(
            (r for r in rows
             if r["n_devices"] == 4 and not r["governor"]
             and not r.get("wall_baseline")
             and r["precision"] == base["precision"]
             and r.get("span") == base.get("span")), None)
        if four is None:
            fails.append(
                f"wall_baseline {base['precision']} (span "
                f"{base.get('span')}) has no span-matched 4-device row")
            continue
        ratio = four["wall_rps"] / max(base["wall_rps"], 1e-9)
        if ratio < wall_floor:
            fails.append(
                f"{four['precision']}: 4-device wall throughput "
                f"{four['wall_rps']} req/s is {ratio:.2f}x the span-"
                f"matched 1-device {base['wall_rps']} req/s — below the "
                f"{wall_floor}x floor (multi-core target {wall_target}x)")
    for r in rows:
        c = r.get("contracts", {})
        if c.get("offered", 0) and c["held"] != c["offered"]:
            fails.append(
                f"{r['n_devices']}dev {r['precision']} gov={r['governor']}: "
                f"only {c['held']}/{c['offered']} energy contracts held")
        if r["governor"] and not c.get("offered", 0):
            fails.append(
                f"{r['n_devices']}dev {r['precision']}: governor row "
                "completed no contract requests (nothing verified)")
    if not any(r["shed"] > 0 for r in rows):
        fails.append("no row shed any request: the closed loop never "
                     "overloaded admission control (arrival_factor bug?)")
    return fails


def wall_summary(data: dict) -> list[str]:
    """Human-readable wall-scaling lines for the bench/gate output."""
    out = []
    rows = data.get("rows", [])
    for base in (r for r in rows if r.get("wall_baseline")):
        four = next(
            (r for r in rows
             if r["n_devices"] == 4 and not r["governor"]
             and not r.get("wall_baseline")
             and r["precision"] == base["precision"]
             and r.get("span") == base.get("span")), None)
        if four is None:
            continue
        ratio = four["wall_rps"] / max(base["wall_rps"], 1e-9)
        out.append(
            f"wall scaling ({base['precision']}, span {base['span']}): "
            f"4-dev {four['wall_rps']} / 1-dev {base['wall_rps']} req/s "
            f"= {ratio:.2f}x (floor 1.0x, multi-core target 1.5x)")
    return out


# --------------------------------------------------------------------------
# CLI + benchmarks.run integration
# --------------------------------------------------------------------------

def run(smoke: bool = True, seed: int = 0, out=OUT_PATH):
    """Bench, write ``out``, yield the report lines; raise when the gate
    fails.  Runs in this process: ``benchmarks.run`` gives every section
    its own process, with the host-device count this bench needs."""
    data = bench(smoke=smoke, seed=seed)
    Path(out).write_text(json.dumps(data, indent=1))
    yield f"[serve_bench] wrote {out} ({len(data['rows'])} rows)"
    for ln in wall_summary(data):
        yield f"[serve_bench] {ln}"
    fails = serve_gate(data)
    if fails:
        raise RuntimeError("[serve_gate] FAIL:\n  " + "\n  ".join(fails))
    yield "[serve_gate] ok"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small grid + short windows (the CI tier-1 run)")
    ap.add_argument("--gate-only", action="store_true",
                    help="re-run the serve gate over an existing "
                         "BENCH_serve.json without re-benchmarking")
    ap.add_argument("--out", default=str(OUT_PATH))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.gate_only:
        data = json.loads(Path(args.out).read_text())
        for ln in wall_summary(data):
            print(f"[serve_gate] {ln}")
        fails = serve_gate(data)
        if fails:
            print("[serve_gate] FAIL:\n  " + "\n  ".join(fails))
            sys.exit(1)
        print("[serve_gate] ok")
        return

    # the forced host-device count must land before jax initializes; when
    # the caller (CI) already set XLA_FLAGS we leave it alone
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_"
                                     "count=4").strip()
    try:
        for ln in run(smoke=args.smoke, seed=args.seed, out=args.out):
            print(ln)
    except RuntimeError as e:
        print(e)
        sys.exit(1)


if __name__ == "__main__":
    main()

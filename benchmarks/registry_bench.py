"""Multi-tenant registry serving bench: N tenants, one process, one cache.

    PYTHONPATH=src python -m benchmarks.registry_bench --smoke

Drives the REAL multi-tenant stack — a ModelRegistry of versioned forest
artifacts, a VMEM-budgeted PackCache, bucket-aware ForestReplicaServer
packed replicas behind a DeviceDispatcher behind a ContinuousBatcher, and a
TenantLedger of per-tenant EnergyGovernors — under Zipf-skewed open-loop
tenant traffic with mixed QoS tiers and mixed precisions, and emits
``BENCH_registry.json``:

* **cache** — hit rate, evictions and peak bytes against the VMEM budget:
  the budget holds the steady-state working set but NOT every (tenant,
  version, precision) bucket the run touches, so the mid-run version
  churn must evict (traffic-weighted) while the measured window stays
  >= 90% hits;
* **swap** — a live ``publish`` hot-swap of the hottest tenant mid-run:
  every request in flight at the swap completes on its pinned version
  (zero loss), and completion p99 latency in the post-swap window must
  not spike vs the pre-swap window;
* **canary** — ``publish(..., canary=f)`` traffic split on another tenant:
  the observed split matches ``f``, per-version ServeStats telemetry
  accumulates on both sides, and ``judge_canary`` prices the delta;
* **tenants** — per-tenant energy isolation: beta is ledgered under a
  budget its fp32 rungs cannot meet, so its governor must walk down to
  an int8 rung and settle there, while alpha's and gamma's governors
  (generous budgets) never move — one tenant's squeeze must not leak.

Single serve device: the data-parallel speedup story is serve_bench's;
this bench isolates the multi-tenant control plane, so virtual time ==
wall time by construction.  Control-plane work (``publish`` writing the
artifact) is NOT charged to the serving clock — a real deployment
publishes from outside the serving process; the cost the serving path
does pay (the new version's cache miss + device placement on first
dispatch) is charged.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

OUT_PATH = Path(__file__).resolve().parent.parent / "BENCH_registry.json"

# Zipf-skewed tenant shares (~1/rank^1.45, normalized): one hot tenant, a
# warm one, a cold one — the cache's traffic-weighted eviction must keep
# the hot buckets resident while stale versions are dropped
TENANTS = (("alpha", 0.62), ("beta", 0.24), ("gamma", 0.14))
# QoS tier mix: gold buys accuracy (higher MaxDiff gate), bulk buys energy
# (explicit int8 tables + early exit), std rides its tenant's governor rung
TIER_MIX = (("std", 0.60), ("gold", 0.20), ("bulk", 0.20))
BASE_THRESH = 0.6
GOLD_THRESH = 0.9
BULK_THRESH = 0.4
# ledger budgets as factors of each tenant's CALIBRATED mixed-traffic
# rung-0 cost: alpha/gamma get headroom (their rungs must NOT move), beta
# is squeezed well under what any fp32 rung can deliver, so its governor
# must walk down to an int8 rung to comply
BUDGET_FACTOR = {"alpha": 1.6, "beta": 0.55, "gamma": 1.7}
SWAP_FRAC = 0.45       # hot-swap the hot tenant at 45% of the run
CANARY_FRAC_AT = 0.70  # start the canary split at 70%
CANARY_FRACTION = 0.25
WARMUP_FRAC = 0.15
WINDOW_FRAC = 0.15     # pre/post swap p99 windows (fraction of requests)


def _percentile(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs, float), q)) if xs else 0.0


def bench(smoke: bool, seed: int = 0, workdir: str | None = None) -> dict:
    import tempfile

    import numpy as np

    from benchmarks.common import forest_for
    from repro.core.grove import split
    from repro.core.policy import FogPolicy
    from repro.data import make_dataset
    from repro.forest.pack import ForestPack
    from repro.registry import ModelRegistry, PackCache
    from repro.serve.dispatch import DeviceDispatcher, ForestReplicaServer
    from repro.serve.governor import (EnergyGovernor, TenantLedger,
                                      default_ladder)
    from repro.serve.scheduler import ContinuousBatcher, Request

    import jax

    n_requests = 1500 if smoke else 6000
    n_slots = 64
    rng = np.random.default_rng(seed)
    ds = make_dataset("penbased")
    n_features = ds.x_test.shape[1]
    gc = split(forest_for("penbased"), 2)
    pack = ForestPack.from_groves(gc, "fp32")

    workdir = workdir or tempfile.mkdtemp(prefix="registry_bench_")
    registry = ModelRegistry(workdir)
    extra = {"n_features_in": n_features}
    for t, _ in TENANTS:
        registry.publish(t, pack, extra=extra)

    # VMEM budget: sized to hold the steady-state working set — every
    # tenant's fp32 + int8 buckets (bulk-tier lanes carry explicit int8
    # contracts, so rare tenants' int8 buckets ARE part of the hot set;
    # with 64 lanes a step, even a 3%-share bucket is dispatched most
    # steps) — but NOT the extra buckets the hot-swap and canary versions
    # bring, so the mid-run churn must evict the stale version's tables
    fp32_b, int8_b = pack.table_bytes, pack.astype("int8").table_bytes
    budget_bytes = 4 * fp32_b + 3 * int8_b
    cache = PackCache(registry, budget_bytes=budget_bytes)
    server = ForestReplicaServer(None, n_features, backend="fused",
                                 registry=registry, cache=cache, seed=seed)
    dispatcher = DeviceDispatcher(server.packed_factory, jax.devices()[:1])

    tenant_names = [t for t, _ in TENANTS]
    tenant_share = np.asarray([s for _, s in TENANTS])
    tenant_share = tenant_share / tenant_share.sum()
    tier_names = [t for t, _ in TIER_MIX]
    tier_share = np.asarray([s for _, s in TIER_MIX])
    tier_share = tier_share / tier_share.sum()

    base = FogPolicy(threshold=BASE_THRESH)
    tenants_of = rng.choice(len(tenant_names), size=n_requests,
                            p=tenant_share)
    tiers_of = rng.choice(tier_names, size=n_requests, p=tier_share)
    beta_bulk = rng.random(n_requests)

    def make_request(rid):
        t = tenant_names[int(tenants_of[rid % len(tenants_of)])]
        if t == "beta":
            # the squeezed tenant's traffic is governed lanes: std (the
            # rung's knobs — the ledger's lever) plus some explicit-int8
            # bulk.  Gold lanes pin their own threshold, which the ladder
            # cannot touch, and would put beta's floor above any budget.
            tier = "bulk" if beta_bulk[rid % len(beta_bulk)] < 0.1 else "std"
        else:
            tier = str(tiers_of[rid % len(tiers_of)])
        pol = None
        if tier == "gold":
            pol = FogPolicy(threshold=GOLD_THRESH)
        elif tier == "bulk":
            pol = FogPolicy(threshold=BULK_THRESH, precision="int8")
        # max_new_tokens=2: every request spans two decode steps, so a
        # hot-swap always catches requests mid-flight — the zero-downtime
        # pinning claim is only tested if something IS in flight
        return Request(rid=rid, prompt=ds.x_test[rid % len(ds.x_test)],
                       model=t, tier=tier, max_new_tokens=2, policy=pol)

    # -- calibration: wave 1 compiles every precision's program and fills
    # the cache; wave 2 (warm) measures serving capacity.  The whole burst
    # also measures each tenant's rung-0 mixed-traffic cost, which sizes
    # the ledger budgets. -------------------------------------------------
    cal = ContinuousBatcher(n_slots, None, server.prefill, eos_id=-1,
                            default_policy=base, dispatcher=dispatcher,
                            registry=registry)
    for rid in range(2 * n_slots):
        cal.submit(make_request(rid))
    cal.run()
    cal_n = 4 * n_slots
    for rid in range(2 * n_slots, 2 * n_slots + cal_n):
        cal.submit(make_request(rid))
    t0 = time.perf_counter()
    cal.run()
    capacity_rps = cal_n / (time.perf_counter() - t0)

    budgets = {}
    ledger = TenantLedger()
    for t in tenant_names:
        m32 = server.energy_model(tenant=t)
        m8 = server.energy_model("int8", tenant=t)
        pj = np.concatenate([
            np.asarray(m32.lane_pj(np.asarray(
                [r.hops[0] for r in cal.completed
                 if r.model == t and r.tier != "bulk"]))),
            np.asarray(m8.lane_pj(np.asarray(
                [r.hops[0] for r in cal.completed
                 if r.model == t and r.tier == "bulk"]))),
        ])
        c_mix = float(pj.mean()) * 1e-3
        budgets[t] = BUDGET_FACTOR[t] * c_mix
        # cooldown longer than the run: a rung measured over budget stays
        # off-limits, so a squeezed tenant SETTLES on its compliant rung
        # instead of periodically re-probing (and flapping through) the
        # rungs that already breached
        ledger.add(t, EnergyGovernor(
            default_ladder(base, m32, budgets[t]), budgets[t],
            model=m32, window=128, patience=2, cooldown=10**9))
    for t in tenant_names:          # calibration traffic is not billed
        registry.stats_for(t, 1).reset()
    cache.stats.reset()

    # -- the measured open loop -------------------------------------------
    b = ContinuousBatcher(n_slots, None, server.prefill, eos_id=-1,
                          default_policy=base, governor=ledger,
                          dispatcher=dispatcher, registry=registry)
    arrival_rps = 0.85 * capacity_rps
    arrivals = np.cumsum(rng.exponential(1.0 / arrival_rps,
                                         size=n_requests))
    warmup_n = int(WARMUP_FRAC * n_requests)
    swap_rid = int(SWAP_FRAC * n_requests)
    canary_rid = int(CANARY_FRAC_AT * n_requests)
    window_n = int(WINDOW_FRAC * n_requests)

    vnow = 0.0
    next_rid = 0
    done_vtime: dict[int, float] = {}
    n_done_seen = 0
    v_measure_start = 0.0
    swap_info: dict = {}
    canary_info: dict = {}
    swapped = canaried = False
    alpha_v2 = beta_canary_v = None
    guard = 0
    while len(b.completed) < n_requests:
        guard += 1
        if guard > 500_000:
            raise RuntimeError("registry_bench loop did not drain")
        if not swapped and next_rid >= swap_rid:
            # control plane: retrain-and-publish of the hot tenant.  The
            # artifact write happens off the serving clock; the serving
            # path pays only the new buckets' cache misses.
            inflight = [s.request.rid for s in b.slots
                        if s.request is not None
                        and s.request.model == "alpha"]
            alpha_v2 = registry.publish("alpha", pack, extra=extra)
            swap_info = {"at_rid": swap_rid, "inflight_rids": inflight}
            swapped = True
        if not canaried and next_rid >= canary_rid:
            # the canary artifact is published at int8 — the denser dtype
            # is the candidate the energy judge should prefer.  Reset the
            # live side's telemetry at the split so the judge compares the
            # SAME traffic window on both sides — live's history includes
            # beta's expensive pre-step-down era, which is not evidence
            # about the candidate.
            registry.stats_for(
                "beta", registry.live_version("beta")).reset()
            beta_canary_v = registry.publish(
                "beta", pack.astype("int8"), extra=extra,
                canary=CANARY_FRACTION)
            canary_info = {"tenant": "beta", "version": beta_canary_v,
                           "fraction": CANARY_FRACTION,
                           "at_rid": canary_rid}
            canaried = True
        while next_rid < n_requests and arrivals[next_rid] <= vnow:
            if next_rid == warmup_n:
                v_measure_start = vnow
                cache.stats.reset()
                b.stats.reset()
            b.submit(make_request(next_rid))
            next_rid += 1
        if b.active == 0 and not b.queue:
            if next_rid < n_requests:
                vnow = max(vnow, float(arrivals[next_rid]))
                continue
            break
        t0 = time.perf_counter()
        b.step()
        vnow += time.perf_counter() - t0
        for r in b.completed[n_done_seen:]:
            done_vtime[r.rid] = vnow
        n_done_seen = len(b.completed)

    b.flush()               # replay any telemetry the loop left buffered

    # -- metrics ----------------------------------------------------------
    completed = {r.rid: r for r in b.completed}
    measured = [r for r in b.completed if r.rid >= warmup_n]
    correct = sum(1 for r in b.completed
                  if r.generated
                  and r.generated[0] == int(ds.y_test[r.rid % len(ds.y_test)]))
    valid = sum(1 for r in b.completed
                if r.generated and r.hops and r.hops[0] >= 1
                and 0 <= r.generated[0] < pack.n_classes)

    def lat_ms(rids):
        return [(done_vtime[rid] - float(arrivals[rid])) * 1e3
                for rid in rids if rid in done_vtime]

    pre = lat_ms(range(max(warmup_n, swap_rid - window_n), swap_rid))
    post = lat_ms(range(swap_rid, swap_rid + window_n))
    inflight_rids = swap_info.get("inflight_rids", [])
    swap_row = {
        "tenant": "alpha", "at_rid": swap_info.get("at_rid"),
        "v_to": alpha_v2,
        "inflight": len(inflight_rids),
        "inflight_completed": sum(1 for rid in inflight_rids
                                  if rid in completed
                                  and completed[rid].done),
        "inflight_on_old_version": sum(
            1 for rid in inflight_rids
            if rid in completed and completed[rid].version == 1),
        "p50_pre_ms": round(_percentile(pre, 50), 3),
        "p99_pre_ms": round(_percentile(pre, 99), 3),
        "p50_post_ms": round(_percentile(post, 50), 3),
        "p99_post_ms": round(_percentile(post, 99), 3),
        "alpha_versions_served": sorted(
            {r.version for r in b.completed if r.model == "alpha"}),
    }

    beta_post = [r for r in b.completed
                 if r.model == "beta" and r.rid >= canary_rid]
    beta_on_canary = [r for r in beta_post if r.version == beta_canary_v]
    judge = registry.judge_canary("beta")
    if judge["canary"]["n_events"] and judge["delta_nj"] <= 0:
        registry.promote("beta")
        promoted = True
    else:
        registry.abort_canary("beta")
        promoted = False
    canary_row = {
        **canary_info,
        "observed_fraction": round(
            len(beta_on_canary) / max(1, len(beta_post)), 4),
        "n_routed": len(beta_on_canary), "n_beta_post": len(beta_post),
        "judge": judge,
        "promoted": promoted,
        "live_after": registry.live_version("beta"),
    }

    tenants_row = {}
    for i, t in enumerate(tenant_names):
        gov = ledger.governor_for(t)
        t_done = [r for r in measured if r.model == t]
        tenants_row[t] = {
            "share": round(float(tenant_share[i]), 4),
            "budget_nj": round(budgets[t], 4),
            "rolling_nj": (None if gov.rolling_nj is None
                           else round(gov.rolling_nj, 4)),
            "rung_final": gov.rung,
            "rung_precision": gov.current.precision,
            "transitions": len(gov.transitions),
            "n_done": len(t_done),
            "mean_hops": round(float(np.mean(
                [r.hops[0] for r in t_done])) if t_done else 0.0, 3),
        }

    v_window = vnow - v_measure_start
    return {
        "dataset": "penbased", "topology": "8x2", "backend": "fused",
        "smoke": smoke, "seed": seed, "n_slots": n_slots,
        "n_requests": n_requests, "warmup_n": warmup_n,
        "capacity_rps": round(capacity_rps, 1),
        "arrival_rps": round(arrival_rps, 1),
        "throughput_rps": round(len(measured) / max(v_window, 1e-9), 1),
        "offered": n_requests, "completed": len(b.completed),
        "shed": len(b.shed_requests),
        "valid": valid,
        "accuracy": round(correct / max(1, len(b.completed)), 4),
        "tiers": b.stats.tier_summary(),
        "tenants": tenants_row,
        "cache": {
            "budget_bytes": budget_bytes,
            "bytes_used": cache.bytes_used,
            "peak_bytes": cache.peak_bytes,
            "hits": cache.stats.hits, "misses": cache.stats.misses,
            "evictions": cache.stats.evictions,
            "hit_rate": round(cache.stats.hit_rate, 4),
            "resident": [list(map(str, k)) for k in cache.keys()],
        },
        "swap": swap_row,
        "canary": canary_row,
    }


# --------------------------------------------------------------------------
# gate
# --------------------------------------------------------------------------

def registry_gate(data: dict) -> list[str]:
    """CI gate over BENCH_registry.json — the acceptance criteria: zero
    request loss across a live hot-swap with no p99 spike, the cache under
    its VMEM budget (with real eviction churn) at >= 90% hits, and
    per-tenant energy isolation (each tenant's steady-state nJ under its
    own budget; the squeezed tenant steps down to int8 alone)."""
    fails = []
    if data.get("completed") != data.get("offered") or data.get("shed"):
        fails.append(
            f"request loss: offered {data.get('offered')} vs completed "
            f"{data.get('completed')} (shed {data.get('shed')})")
    if data.get("valid") != data.get("completed"):
        fails.append(f"only {data.get('valid')}/{data.get('completed')} "
                     "completions were valid (hops>=1, in-range label)")
    if data.get("accuracy", 0.0) < 0.8:
        fails.append(f"end-to-end accuracy {data.get('accuracy')} < 0.8 — "
                     "some bucket served the wrong tables")

    sw = data.get("swap", {})
    if sw.get("inflight_completed") != sw.get("inflight"):
        fails.append(
            f"hot-swap dropped in-flight requests: "
            f"{sw.get('inflight_completed')}/{sw.get('inflight')} completed")
    if sw.get("inflight_on_old_version") != sw.get("inflight"):
        fails.append(
            "hot-swap migrated in-flight requests off their pinned "
            f"version: {sw.get('inflight_on_old_version')}/"
            f"{sw.get('inflight')} stayed on v1")
    p99_pre, p99_post = sw.get("p99_pre_ms", 0.0), sw.get("p99_post_ms", 0.0)
    if p99_post > max(1.5 * p99_pre, p99_pre + 5.0):
        fails.append(
            f"hot-swap p99 spike: {p99_post}ms post vs {p99_pre}ms pre "
            "(allowed 1.5x or +5ms)")
    if len(sw.get("alpha_versions_served", [])) < 2:
        fails.append("hot-swap never served the new version "
                     f"(versions {sw.get('alpha_versions_served')})")

    c = data.get("cache", {})
    if c.get("peak_bytes", 0) > c.get("budget_bytes", 0):
        fails.append(f"cache exceeded its VMEM budget: peak "
                     f"{c.get('peak_bytes')} > {c.get('budget_bytes')} B")
    if c.get("evictions", 0) < 1:
        fails.append("cache never evicted: the run's bucket set did not "
                     "exceed the budget (nothing was measured)")
    if c.get("hit_rate", 0.0) < 0.90:
        fails.append(f"cache hit rate {c.get('hit_rate')} < 0.90 under "
                     "Zipf tenant traffic")

    tenants = data.get("tenants", {})
    for t, row in tenants.items():
        if (row.get("rolling_nj") is not None
                and row["rolling_nj"] > row["budget_nj"]):
            fails.append(
                f"tenant {t}: steady-state {row['rolling_nj']} nJ over "
                f"its own budget {row['budget_nj']} nJ")
    if tenants.get("beta", {}).get("rung_precision") != "int8":
        fails.append("beta's squeezed governor never stepped down to an "
                     "int8 rung (per-tenant governance is inert)")
    for t in ("alpha", "gamma"):
        if tenants.get(t, {}).get("transitions", 1) != 0:
            fails.append(
                f"tenant {t}'s governor moved "
                f"({tenants.get(t, {}).get('transitions')} transitions) — "
                "beta's squeeze leaked across the ledger")

    cn = data.get("canary", {})
    target = cn.get("fraction", 0.0)
    if abs(cn.get("observed_fraction", 0.0) - target) > 0.12:
        fails.append(
            f"canary split off target: observed "
            f"{cn.get('observed_fraction')} vs fraction {target}")
    judge = cn.get("judge", {})
    if not (judge.get("live", {}).get("n_events", 0)
            and judge.get("canary", {}).get("n_events", 0)):
        fails.append("canary judging has no per-version telemetry on "
                     "one side of the split")
    return fails


# --------------------------------------------------------------------------
# CLI + benchmarks.run integration
# --------------------------------------------------------------------------

def run(smoke: bool = True, seed: int = 0, workdir=None, out=OUT_PATH):
    """Bench, write ``out``, yield the report lines; raise when the gate
    fails.  Runs in this process (``benchmarks.run`` gives every section
    its own process)."""
    data = bench(smoke=smoke, seed=seed, workdir=workdir)
    Path(out).write_text(json.dumps(data, indent=1))
    c, sw = data["cache"], data["swap"]
    yield (f"[registry_bench] {len(data['tenants'])} tenants, "
           f"{data['completed']}/{data['offered']} done, "
           f"acc {data['accuracy']}, {data['throughput_rps']} req/s")
    yield (f"[registry_bench] cache hit {c['hit_rate']}, "
           f"{c['evictions']} evictions, peak {c['peak_bytes']}/"
           f"{c['budget_bytes']} B")
    yield (f"[registry_bench] swap p99 {sw['p99_pre_ms']}ms -> "
           f"{sw['p99_post_ms']}ms, inflight {sw['inflight_completed']}/"
           f"{sw['inflight']} done, on v1 {sw['inflight_on_old_version']}")
    for t, row in data["tenants"].items():
        yield (f"[registry_bench] {t}: budget {row['budget_nj']} nJ, "
               f"rolling {row['rolling_nj']} nJ, rung {row['rung_final']} "
               f"({row['rung_precision'] or 'fp32'}), "
               f"{row['transitions']} transitions")
    yield (f"[registry_bench] canary observed "
           f"{data['canary']['observed_fraction']} vs "
           f"{data['canary']['fraction']}, promoted "
           f"{data['canary']['promoted']}")
    yield f"[registry_bench] wrote {out}"
    fails = registry_gate(data)
    if fails:
        raise RuntimeError("[registry_gate] FAIL:\n  " + "\n  ".join(fails))
    yield "[registry_gate] ok"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short run (the CI tier-1 configuration)")
    ap.add_argument("--gate-only", action="store_true",
                    help="re-run the gate over an existing "
                         "BENCH_registry.json without re-benchmarking")
    ap.add_argument("--out", default=str(OUT_PATH))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", default=None,
                    help="registry directory (default: a fresh tempdir)")
    args = ap.parse_args()

    if args.gate_only:
        data = json.loads(Path(args.out).read_text())
        fails = registry_gate(data)
        if fails:
            print("[registry_gate] FAIL:\n  " + "\n  ".join(fails))
            sys.exit(1)
        print("[registry_gate] ok")
        return

    try:
        for ln in run(smoke=args.smoke, seed=args.seed, workdir=args.workdir,
                      out=args.out):
            print(ln)
    except RuntimeError as e:
        print(e)
        sys.exit(1)


if __name__ == "__main__":
    main()

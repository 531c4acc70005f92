"""One run of one serving cell: set-up, the measured window, the metrics
and the comparison with the reference."""
from __future__ import annotations

import dataclasses
import gc
import shutil
import time
from pathlib import Path

import numpy as np

from harness import reference, roofline, serve, trace, traffic
from harness.field import grow_field
from harness.spec import BENCH, metric_readers
from harness.twin import make_twin

WARM_RID = 10**12          # warm-up request ids start here
# the control: the configuration's precision replaced by the nearest one
# below it, through the program's own path where it has one (a bf16 pack
# served for an fp32 tenant) and else through the reference put in the
# program's place (int4 tables for an int8 tenant)
CONTROL_PUBLISH = {"fp32": "bf16"}
CONTROL_REFERENCE = {"int8": "int4"}
# kernels whose device time the trace reduction sums (matched by name)
KERNELS = ("fused_fog",)


@dataclasses.dataclass
class Run:
    """What a metric reader may read."""
    loop: str
    n_chips: int
    window: serve.Window
    calls: dict
    trace: trace.TraceSummary | None
    trace_window_s: float | None
    peak: dict
    completed_in_window: int


class CompileCounter:
    """Counts jit traces and backend compiles while ``on`` (none should
    happen in the measured window)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        self._fn = self._note
        jax.monitoring.register_event_duration_secs_listener(self._fn)

    def _note(self, name, _secs, **_kw):
        if self.on and name in self.EVENTS:
            self.n += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._fn)


class Tracer:
    def __init__(self, log_dir: Path):
        self.dir = log_dir
        self.t0 = self.t1 = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()


def _calls(rec, window, served, tenants_prec, field_dims, n_features):
    """Per evaluation call harvested in the window: its lanes, and the
    algorithmic ops (from the hops its requests took) and bytes."""
    G, k, depth, C, T = field_dims
    key = served["replica"] * (1 << 32) + served["dispatch"]
    uk, inv = np.unique(key, return_inverse=True)
    hop_sum = np.bincount(inv, weights=served["hops"].clip(0))
    lookup = dict(zip(uk.tolist(), hop_sum.tolist()))
    count: dict[int, int] = {}
    out = {"lanes": [], "ops": [], "bytes": []}
    hop_ops = roofline.hop_ops(k, depth, C)
    for h, (_, pend) in enumerate(rec.harvests):
        for p in pend:
            count[p.device] = count.get(p.device, 0) + 1
            if not window.harvest_lo <= h < window.harvest_hi:
                continue
            hops = lookup.get(p.device * (1 << 32) + count[p.device], 0.0)
            prec = p.precision or tenants_prec[0]
            tb = roofline.table_bytes(T, depth, C, prec)
            out["lanes"].append(len(p.lanes))
            out["ops"].append(hops * hop_ops)
            out["bytes"].append(roofline.call_bytes(tb, len(p.lanes),
                                                    n_features))
    return {k_: np.asarray(v, np.float64) for k_, v in out.items()}


def _sample(served, size: int, seed: int) -> np.ndarray:
    """Indices of a sample drawn from the seed, the same share from every
    replica."""
    rng = np.random.default_rng(seed)
    reps = np.unique(served["replica"])
    per = max(1, size // max(1, len(reps)))
    idx = []
    for r in reps:
        mine = np.flatnonzero(served["replica"] == r)
        idx.append(mine if len(mine) <= per
                   else rng.choice(mine, per, replace=False))
    return np.sort(np.concatenate(idx)) if idx else np.zeros(0, np.int64)


def check(served, rows_of, x_test, field, tenants, threshold, n_groves,
          span, server_seed, sample, control=False):
    """The compared numbers: off-edge disagreements with the reference per
    tenant precision, over the sampled requests."""
    feature, thr_t, leaf = (np.asarray(a) for a in field)
    rid = served["rid"][sample]
    t_idx = rid % len(tenants)
    start = np.empty(len(sample), np.int64)
    rep, num, lane = (served[k][sample] for k in ("replica", "dispatch",
                                                   "lane"))
    for r in np.unique(rep):
        m = rep == r
        nums, inv = np.unique(num[m], return_inverse=True)
        draws = reference.start_draws(server_seed, int(r), nums, span,
                                      n_groves)
        start[m] = draws[inv, lane[m]]
    x = x_test[rows_of(rid)]
    label_p = served["label"][sample].copy()
    hops_p = served["hops"][sample].copy()
    out = {}
    for i, t in enumerate(tenants):
        m = t_idx == i
        prec = t["precision"]
        thr_q, leaf_q = reference.tables(thr_t, leaf, prec)
        labels_r, margins_r = reference.evaluate(feature, thr_q, leaf_q,
                                                 x[m], start[m], n_groves)
        if control and prec in CONTROL_REFERENCE:
            thr_c, leaf_c = reference.tables(thr_t, leaf,
                                             CONTROL_REFERENCE[prec])
            lc, mc = reference.evaluate(feature, thr_c, leaf_c, x[m],
                                        start[m], n_groves)
            label_p[m], hops_p[m] = reference.gate(lc, mc, threshold)
        bad = reference.off_edge(label_p[m], hops_p[m], labels_r, margins_r,
                                 threshold)
        out[f"offedge_{prec}"] = int(bad.sum())
    return out


def run_serving(work, cfg, *, seed: int, seconds: float, trace_on: bool,
                devices, t_process: float, control: bool = False,
                root: Path = BENCH, run_dir: Path | None = None, log=print):
    import jax
    run_dir = run_dir or root / ".run"
    ds, fld, tenants = cfg["dataset"], cfg["field"], cfg["tenants"]
    chips = int(cfg["chips"])
    devices = list(devices)[:chips]
    thr = float(work["threshold"])
    F, C = int(ds["n_features"]), int(ds["n_classes"])
    T, k, depth = int(fld["n_trees"]), int(fld["grove_size"]), int(
        fld["depth"])
    G = T // k

    twin = make_twin(ds, traffic.sub_seed(seed, 1))
    field = grow_field(twin.x_train, twin.y_train, C, n_trees=T, depth=depth,
                       seed=traffic.sub_seed(seed, 2))
    field = tuple(np.asarray(a) for a in field)
    publish = [dict(t, precision=CONTROL_PUBLISH.get(t["precision"],
                                                     t["precision"]))
               if control else t for t in tenants]
    registry = serve.publish(run_dir / "registry", field, k, publish, F)
    server_seed = traffic.sub_seed(seed, 3)
    plane = serve.build_plane(registry, F, int(cfg["slots"]), devices, thr,
                              server_seed)
    rec = serve.Recorder(plane.batcher)
    reqs = serve.Traffic(twin.x_test, tenants, thr)
    rng_w, rng_warm = traffic.streams(seed, 2)
    n_test = len(twin.x_test)
    loop = work["loop"]
    backlog = int(work.get("backlog_per_slot", 2)) * plane.n_slots
    serve.warm_up(plane, rec, reqs, WARM_RID, loop,
                  rate=work.get("rate_per_s"),
                  seconds=float(work.get("warmup_s", 2.0)), backlog=backlog,
                  seed=int(rng_warm.integers(2**31)))
    tracer = Tracer(run_dir / "trace") if trace_on else None
    rec.annotate = trace_on
    compiles = CompileCounter()
    compiles.on = True
    if loop == "open":
        due, rows = traffic.open_schedule(rng_w, float(work["rate_per_s"]),
                                          seconds, n_test)
        w = serve.run_open(plane, rec, reqs, due, rows, seconds,
                           tracer=tracer)
        rid_hi = w.n_due
        rows_of = lambda rid: rows[rid]
    elif loop == "closed":
        rows = traffic.row_stream(rng_w, n_test, 1 << 20)
        w = serve.run_closed(plane, rec, reqs, rows, seconds,
                             backlog=backlog, tracer=tracer)
        rid_hi = w.n_submitted
        rows_of = lambda rid: rows[rid % len(rows)]
    else:
        raise ValueError(f"unknown loop {loop!r}")
    rec.annotate = False
    compiles.on = False
    compiles.close()
    setup_s = w.t_start - t_process
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)

    try:
        served = serve.served(rec, plane.batcher.completed, rid_hi,
                              plane.span)
        unmapped = 0
    except ValueError as e:
        log(f"[check] {e}")
        served, unmapped = None, 1
    precs = [t["precision"] for t in tenants]
    calls = (_calls(rec, w, served, precs, (G, k, depth, C, T), F)
             if served is not None else None)
    summary = None
    if tracer is not None:
        summary = trace.summarize(trace.find_xplane(tracer.dir),
                                  kernels=KERNELS)
    del plane, rec, reqs
    gc.collect()

    if w.steps:
        host = " ".join(f"{k}={v / w.steps / 1e3:.1f}"
                        for k, v in w.phase_ns.items())
        lanes = calls["lanes"] if calls is not None else np.zeros(0)
        log(f"[window] steps={w.steps} calls={len(lanes)} lanes/call="
            f"{lanes.mean() if len(lanes) else 0:.1f} host us/step {host} "
            f"backlog={[(round(t, 3), n) for t, n in w.backlog]}")
    attempted = rid_hi
    e2e, checks = {}, {}
    tail_ms = None      # p99 latency: the sweep's, no end-to-end metric
    completed = in_window = 0
    if served is not None:
        done = np.isfinite(served["t_done"])
        completed = int(done.sum())
        in_window = int((served["t_done"][done] <= w.t_end).sum())
        if loop == "open":
            lat = np.full((attempted,), np.inf)
            r = served["rid"][done]
            lat[r] = served["t_done"][done] - w.due_abs[r]
            p50, p99 = np.percentile(lat, [50, 99]) * 1e3
            if np.isfinite(p99):
                e2e["p50_ms"] = (float(p50), "ms")
                tail_ms = float(p99)
        e2e["req_per_s"] = (in_window / seconds, "req/s")
        sample = _sample(served, int(work.get("check_sample", 32768)),
                         traffic.sub_seed(seed, 4))
        for name, v in check(served, rows_of, twin.x_test, field, tenants,
                             thr, G, int(cfg["slots"]), server_seed, sample,
                             control=control).items():
            checks[name] = (v, work["limits"][name])
    checks["missing"] = (attempted - completed, 0)
    checks["unmapped"] = (unmapped, 0)
    e2e["setup_s"] = (setup_s, "s")
    out = dict(checks=checks, attempted=attempted,
               failed=attempted - completed, memory_peak_bytes=int(mem),
               e2e=e2e, p99_ms=tail_ms, window=w,
               compiles_in_window=compiles.n)
    if trace_on:
        run = Run(loop=loop, n_chips=chips, window=w, calls=calls,
                  trace=summary,
                  trace_window_s=tracer.t1 - tracer.t0,
                  peak=roofline.peaks(devices[0].device_kind),
                  completed_in_window=in_window)
        out["per_layer"] = {m.name: (v, m.unit) for m in metric_readers()
                            if (v := m.read(run)) is not None}
        out["busy_s"] = float(np.mean(summary.busy_s)) if summary.busy_s \
            else 0.0
        out["window_s"] = run.trace_window_s
        out["breakdown"] = {"device_ops": trace.top(summary.op_seconds),
                            "idle_gaps": trace.top(summary.idle_by_host)}
    return out

"""Run a serving cell traced with the program's own serving-step
instrumentation on, in one process on the chip this process finds.

    python bench/serve_trace.py --workload <cell> --seconds <s> --seeds 1,2,3

Each run is ``bench/run.py --trace 1``'s run, except that for the traced
window the batcher's ``trace`` is on: ``serve.*`` spans beside the
benchmark's, collector pauses, lane-hop counts.  The harness itself never
turns it on; this script does, by wrapping ``serve.build_plane`` (to find
the batcher) and ``cell.Tracer`` (whose start and stop bound the window).

One JSON line per run on standard output: the per-layer metrics the
readers find, host microseconds per step of every ``phase_ns`` key, and
the numbers the program's new counters give (queue wait p50, collector
ms per window second, the fused kernel's hop yield); before it, on
standard error, the ``[idle-by-span]`` split of the chip's idle time by
innermost ``serve.*`` span (``harness.spans``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run  # noqa: E402  (bench/ is this script's directory)


def traced(work, cfg, *, seed, seconds, devices, log):
    """One ``run_serving`` with the program's tracing on in the window;
    returns its output, the batcher and the window's lane-hop counts."""
    from harness import cell, serve
    plane, counts = {}, {}
    build, tracer = serve.build_plane, cell.Tracer

    def build_plane(*args, **kw):
        plane["p"] = build(*args, **kw)
        return plane["p"]

    class ProgramTracer(tracer):
        def start(self):
            b = plane["p"].batcher
            b.trace = True
            counts.update(b.counts)
            super().start()

        def stop(self):
            super().stop()
            b = plane["p"].batcher
            b.trace = False
            for k, v in b.counts.items():
                counts[k] = v - counts[k]

    serve.build_plane, cell.Tracer = build_plane, ProgramTracer
    try:
        out = cell.run_serving(work, cfg, seed=seed, seconds=seconds,
                               trace_on=True, devices=devices,
                               t_process=time.perf_counter(),
                               run_dir=run.BENCH / ".run", log=log)
    finally:
        serve.build_plane, cell.Tracer = build, tracer
    return out, plane["p"].batcher, counts


def program_numbers(out, batcher, counts) -> dict:
    """What the program's counters say about the window."""
    w = out["window"]
    rid_hi = w.n_due if w.due_abs is not None else w.n_submitted
    wait = [r.t_admit - r.t_submit for r in batcher.completed
            if r.rid < rid_hi and r.t_admit is not None]
    walked = counts.get("lane_hops_walked", 0)
    return {
        "phase_us_per_step": {k: v / w.steps / 1e3
                              for k, v in w.phase_ns.items()}
        if w.steps else {},
        "queue_wait_ms_p50": float(np.median(wait)) * 1e3 if wait else None,
        "gc_ms_per_s": w.phase_ns.get("gc", 0) / 1e6 / w.seconds,
        "lane_hops": counts,
        "hop_yield_pct": 100.0 * counts["lane_hops_served"] / walked
        if walked else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.REPO / "src"))
    sys.path.insert(0, str(run.BENCH))
    from harness.spec import load_cell
    work, cfg = load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(cfg["chips"]):
        print("serve_trace: refusing to run without the cell's TPU chips",
              file=sys.stderr)
        return 1
    run.use_compile_cache(run.BENCH)
    from harness import spans, trace
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    for seed in (int(s) for s in args.seeds.split(",")):
        out, batcher, counts = traced(work, cfg, seed=seed,
                                      seconds=args.seconds,
                                      devices=devices, log=log)
        idle = spans.idle_by_span(
            trace.find_xplane(run.BENCH / ".run" / "trace"))
        line = run.result_line(out, True, devices)
        print(json.dumps({
            "seed": seed, "correct": line["correct"],
            "metrics": {k: m["value"] for k, m in line["metrics"].items()},
            "device": line["device"],
            **program_numbers(out, batcher, counts),
            "idle_by_span": idle}), flush=True)
        del out, batcher
    return 0


if __name__ == "__main__":
    sys.exit(main())

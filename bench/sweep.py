"""Sweep an open-loop cell's arrival rate to find its knee, in one
process on the chip this process finds.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 10000,20000,40000

For each rate: the rate offered and completed in the window, the backlog
(queued + in-slot requests) at each eighth of the window, p50 / p99 and
whether the answers were correct.  The knee is the highest rate whose
backlog does not grow through the window.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402  (bench/ is this script's directory)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.REPO / "src"))
    sys.path.insert(0, str(run.BENCH))
    from harness.spec import load_cell
    work, cfg = load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(cfg["chips"]):
        print("sweep: refusing to run without the cell's TPU chips",
              file=sys.stderr)
        return 1
    run.use_compile_cache(run.BENCH)
    from harness.cell import run_serving
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        w = dict(work, rate_per_s=rate)
        out = run_serving(w, cfg, seed=args.seed + i, seconds=args.seconds,
                          trace_on=False, devices=devices,
                          t_process=time.perf_counter())
        e2e = {k: v for k, (v, _) in out["e2e"].items()}
        print(json.dumps({
            "rate": rate, "req_per_s": e2e.get("req_per_s"),
            "p50_ms": e2e.get("p50_ms"), "p99_ms": out["p99_ms"],
            "backlog": [[round(t, 3), n] for t, n in out["window"].backlog],
            "failed": out["failed"],
            "checks": {k: v for k, (v, _) in out["checks"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

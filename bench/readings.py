"""Readings of the numbers ``correct`` compares, for setting their limits:
the program on many seeds and the control on a few, in one process on the
chip this process finds (set-up compiles once).

    python bench/readings.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 --control-seeds 4,5,6

The control is the configuration's precision replaced by the next one
below it (``harness.cell.CONTROL_PUBLISH`` / ``CONTROL_REFERENCE``).  One
JSON line per run: the seed, whether it was the control, each compared
number and the end-to-end metrics.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run  # noqa: E402  (bench/ is this script's directory)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.REPO / "src"))
    sys.path.insert(0, str(run.BENCH))
    from harness.spec import load_cell
    work, cfg = load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(cfg["chips"]):
        print("readings: refusing to run without the cell's TPU chips",
              file=sys.stderr)
        return 1
    run.use_compile_cache(run.BENCH)
    from harness.cell import run_serving
    todo = [(int(s), False) for s in args.seeds.split(",") if s] + [
        (int(s), True) for s in args.control_seeds.split(",") if s]
    for seed, control in todo:
        out = run_serving(work, cfg, seed=seed, seconds=args.seconds,
                          trace_on=False, devices=devices,
                          t_process=time.perf_counter(), control=control)
        print(json.dumps({
            "seed": seed, "control": control,
            "checks": {k: v for k, (v, _) in out["checks"].items()},
            "e2e": {k: v for k, (v, _) in out["e2e"].items()},
            "attempted": out["attempted"], "failed": out["failed"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

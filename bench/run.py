"""Run one benchmark cell once on the accelerator this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json`` and its configuration
``bench/configs/<config>.json``.  With ``--trace 0`` the result's metrics
are the cell's end-to-end metrics (host clock); with ``--trace 1`` the
window is traced and the metrics are the per-layer metrics that the
readers under ``bench/metrics/`` find something to read for.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``) and, last, ``checks``: each number compared with the
reference beside its limit.  The same numbers close standard error.
Without a TPU, or with fewer chips than the cell asks for, the run prints
no result and exits non-zero.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


def _fail(msg: str, code: int) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return code


def use_compile_cache(bench: Path) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else a fixed directory of the checkout; every program is
    kept, however quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        bench / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def result_line(out: dict, trace_on: bool, devices) -> dict:
    checks = out["checks"]
    correct = all(v <= lim for v, lim in checks.values())
    metrics = out["per_layer"] if trace_on else out["e2e"]
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    if trace_on:
        device["busy_s"] = out["busy_s"]
        device["window_s"] = out["window_s"]
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"],
            "metrics": {k: {"value": v, "unit": u}
                        for k, (v, u) in metrics.items()},
            "device": device}
    if trace_on:
        line["breakdown"] = out["breakdown"]
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (REPO / "src" / "repro").is_dir():
        return _fail(f"the program's sources are missing ({REPO / 'src'})",
                     2)
    sys.path.insert(0, str(REPO / "src"))
    sys.path.insert(0, str(BENCH))
    from harness.spec import SpecError, load_cell
    try:
        work, cfg = load_cell(args.workload)
    except SpecError as e:
        return _fail(str(e), 2)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"refusing to run: no TPU (JAX runs on "
                     f"{devices[0].platform})", 1)
    if len(devices) < int(cfg["chips"]):
        return _fail(f"refusing to run: {args.workload} needs "
                     f"{cfg['chips']} chips, JAX sees {len(devices)}", 1)
    use_compile_cache(BENCH)
    from harness.cell import run_serving
    out = run_serving(work, cfg, seed=args.seed, seconds=args.seconds,
                      trace_on=bool(args.trace), devices=devices,
                      t_process=T_PROCESS,
                      log=lambda m: print(m, file=sys.stderr, flush=True))
    line = result_line(out, bool(args.trace), devices)
    print(f"compiles in the window: {out['compiles_in_window']}",
          file=sys.stderr, flush=True)
    for k, c in line["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

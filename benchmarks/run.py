"""Benchmark runner: one section per paper table/figure (+ beyond-paper).

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only a,b]

Prints CSV blocks.  Every section runs in its own child process, one after
another, with this process's environment: the parent never touches JAX, so
the child that runs a section is the one process holding the device.
Sections that need several devices on a CPU host (``serve``: 4,
``fog_ring``: 8) get that many forced host devices in their child; the flag
only affects the CPU platform.  The roofline table is produced by the
dry-run (launch/dryrun.py) since it needs 512 forced host devices.
"""
from __future__ import annotations

import argparse
import importlib
import os
import subprocess
import sys
import time

# section -> (module, callable, forced host devices or None)
SECTIONS = {
    "engine": ("engine_bench", "run", None),
    "table1_accuracy": ("table1_accuracy", "run", None),
    "table1_energy": ("table1_energy", "run", None),
    "fig4_topology": ("fig4_topology", "run", None),
    "fig5_threshold": ("fig5_threshold", "run", None),
    "fog_ring": ("fog_ring_bench", "run", 8),
    "ablation_adaptive": ("ablation_adaptive", "run", None),
    "lm_fog_exit": ("lm_fog_exit", "run", None),
    # data-parallel serving plane across replicas
    "serve": ("serve_bench", "run", 4),
    # multi-tenant registry serving
    "registry": ("registry_bench", "run", None),
    # host vs device trainer; full mode runs the train_gate
    "train": ("train_bench", "run", None),
}


def run_section(name: str, quick: bool) -> None:
    """Run one section in THIS process and print its rows."""
    module, fn, n_host = SECTIONS[name]
    if n_host and "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_host}").strip()
    from repro.launch.compile_cache import use_repo_compile_cache
    use_repo_compile_cache()
    import benchmarks.common as common
    if quick:
        common.DATASETS = ["penbased", "segmentation"]
    section = getattr(importlib.import_module(f"benchmarks.{module}"), fn)
    if name == "fig5_threshold":
        rows = section(common.DATASETS)
    elif name in ("serve", "registry", "train"):
        rows = section(smoke=quick)
    else:
        rows = section()
    for row in rows:
        print(row, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="subset of datasets for a fast pass")
    ap.add_argument("--only", default=None,
                    help="comma-separated section names")
    ap.add_argument("--section", choices=sorted(SECTIONS),
                    help=argparse.SUPPRESS)   # the per-section child
    args = ap.parse_args()
    if args.section:
        run_section(args.section, args.quick)
        return

    only = set(args.only.split(",")) if args.only else None
    failures = []
    for name in SECTIONS:
        if only and name not in only:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        cmd = [sys.executable, "-m", "benchmarks.run", "--section", name]
        if args.quick:
            cmd.append("--quick")
        if subprocess.run(cmd).returncode != 0:
            failures.append(name)
        print(f"----- {name} done in {time.time() - t0:.1f}s", flush=True)
    if failures:
        print(f"\nFAILED sections: {failures}", file=sys.stderr)
        sys.exit(1)
    print("\nall benchmark sections passed")


if __name__ == "__main__":
    main()

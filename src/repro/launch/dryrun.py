import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax-touching import: jax locks device count at first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the sharded step (train_step for train shapes,
prefill/decode for serving shapes), lowers it with abstract inputs
(ShapeDtypeStruct — zero allocation), compiles it for the production mesh,
and records memory_analysis / cost_analysis / collective schedule for the
roofline report.  A failure here (sharding mismatch, OOM at compile,
unsupported collective) is a bug in the system.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --all [--multipod] [--out results.jsonl]
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import ARCHS, get_arch
from repro.launch.mesh import make_production_mesh
from repro.launch import hlo_cost as R
from repro.train.loop import SHAPES, input_specs, make_train_step_lowerable, shape_supported


def dryrun_cell(arch: str, shape: str, *, multi_pod: bool = False,
                fog: bool = False, accum_steps: int = 1,
                verbose: bool = True) -> dict:
    """Lower+compile one cell; returns a result record (raises on failure)."""
    cfg = get_arch(arch)
    ok, why = shape_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "skipped": True, "why": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = 512 if multi_pod else 256
    sp = SHAPES[shape]
    t0 = time.time()

    with jax.set_mesh(mesh):
        if sp.kind == "train":
            jitted, (params_shape, opt_shape, batch_shape) = \
                make_train_step_lowerable(cfg, mesh, shape,
                                          accum_steps=accum_steps)
            lowered = jitted.lower(params_shape, opt_shape, batch_shape)
        elif sp.kind == "prefill":
            from repro.serve.decode import make_prefill_step
            jitted, (params_shape, inp) = make_prefill_step(cfg, mesh, shape)
            key = "embeds" if cfg.frontend else "tokens"
            lowered = jitted.lower(params_shape, inp[key])
        else:  # decode
            from functools import partial
            import jax.numpy as jnp
            from repro.models import transformer as T
            from repro.serve.decode import make_serve_step
            jitted, (params_shape, cache_shape, inp) = make_serve_step(
                cfg, mesh, shape, fog=fog)
            x_shape = inp["embeds"] if cfg.frontend else inp["token"]
            # fog decode takes the per-lane runtime knobs as traced inputs
            knobs = (inp["fog_thresh"], inp["fog_budget"]) if fog else ()
            lowered = jitted.lower(params_shape, cache_shape,
                                   x_shape, inp["length"], *knobs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    text = compiled.as_text()
    terms = R.extract(compiled, text, arch=arch, shape=shape,
                      mesh_name=mesh_name, chips=chips, cfg=cfg)
    mem = compiled.memory_analysis()
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "chips": chips,
        "fog": fog, "accum_steps": accum_steps, "skipped": False,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "hlo_flops": terms.hlo_flops, "hlo_bytes": terms.hlo_bytes,
        "collective_bytes": terms.collective_bytes,
        "collective_by_kind": terms.collective_by_kind,
        "model_flops": terms.model_flops,
        "compute_s": terms.compute_s, "memory_s": terms.memory_s,
        "collective_s": terms.collective_s, "dominant": terms.dominant,
        "tile_bytes": terms.tile_bytes,
        "memory_s_fused": terms.memory_s_fused,
        "useful_flops_ratio": terms.useful_flops_ratio,
        "roofline_fraction": terms.roofline_fraction,
        "bytes_per_device": terms.bytes_per_device,
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape} x {mesh_name}"
              f"{' (fog)' if fog else ''}: "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s | "
              f"compute {terms.compute_s*1e3:.1f}ms "
              f"memory {terms.memory_s*1e3:.1f}ms "
              f"collective {terms.collective_s*1e3:.1f}ms "
              f"-> {terms.dominant}-bound | "
              f"temp/dev {rec['temp_bytes'] and rec['temp_bytes']/2**30:.2f}GiB",
              flush=True)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--fog", action="store_true",
                    help="lower the FoG early-exit decode step")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()

    cells: list[tuple[str, str]]
    if args.all:
        cells = [(a, s) for a in sorted(ARCHS) for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch + --shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape in cells:
        try:
            rec = dryrun_cell(arch, shape, multi_pod=args.multipod,
                              fog=args.fog)
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failures.append((arch, shape, str(e)))
            rec = {"arch": arch, "shape": shape, "error": str(e)}
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    if failures:
        print(f"FAILED cells: {failures}", file=sys.stderr)
        return 1
    print("all cells passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""A cell small enough to run on the CPU (the fused kernel interpreted),
driven through the same harness as the chip."""
from __future__ import annotations

import copy
import json
import time

from conftest import BENCH


def tiny_cell(workload: str = "pen-8x2.gate.open", *, dataset=None,
              field=None, **over):
    work = json.loads((BENCH / "workloads" / f"{workload}.json").read_text())
    cfg = json.loads((BENCH / "configs" / f"{work['config']}.json")
                     .read_text())
    cfg = copy.deepcopy(cfg)
    cfg["dataset"].update(n_train=1500, n_test=300)
    cfg["field"].update(n_trees=8, grove_size=2, depth=5)
    cfg["dataset"].update(dataset or {})
    cfg["field"].update(field or {})
    cfg["slots"] = 128
    work.update(warmup_s=0.3, check_sample=2000)
    if work["loop"] == "open":
        work["rate_per_s"] = 300
    work.update(over)
    return work, cfg


def run_tiny(tmp_path, workload="pen-8x2.gate.open", seconds=1.0,
             control=False, devices=None, chips=None, dataset=None,
             field=None, **over):
    import jax
    from harness.cell import run_serving
    work, cfg = tiny_cell(workload, dataset=dataset, field=field, **over)
    if chips is not None:
        cfg["chips"] = chips
    return run_serving(work, cfg, seed=2**33 + 11, seconds=seconds,
                       trace_on=False, devices=devices or jax.devices(),
                       t_process=time.perf_counter(), control=control,
                       run_dir=tmp_path / "run", log=lambda m: None)


def correct(out) -> bool:
    return all(v <= lim for v, lim in out["checks"].values())

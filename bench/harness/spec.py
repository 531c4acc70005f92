"""Finding a cell's files by name.

A cell (workload) is ``workloads/<cell>.json``; it names its configuration,
``configs/<config>.json``.  A per-layer metric is ``metrics/<metric>.py``.
Nothing here knows any cell, configuration or metric by name.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


class SpecError(ValueError):
    """A workload, configuration or metric file is missing or malformed."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"no such file: {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: {e}") from None


def load_cell(name: str, root: Path = BENCH) -> tuple[dict, dict]:
    """(workload, configuration) of the cell ``name``."""
    work = load_json(root / "workloads" / f"{name}.json")
    work["name"] = name
    for key in ("config", "loop"):
        if key not in work:
            raise SpecError(f"workload {name} has no {key!r}")
    cfg = load_json(root / "configs" / f"{work['config']}.json")
    cfg["name"] = work["config"]
    for key in ("dataset", "field", "tenants", "slots", "chips"):
        if key not in cfg:
            raise SpecError(f"configuration {work['config']} has no {key!r}")
    return work, cfg


class MetricReader:
    """One per-layer metric: its declared layer, unit, source, the
    end-to-end metric it moves, and ``read(run) -> float | None``."""

    def __init__(self, name: str, module):
        self.name = name
        for attr in ("LAYER", "UNIT", "BETTER", "SOURCE", "MOVES", "read"):
            if not hasattr(module, attr):
                raise SpecError(f"metric {name} defines no {attr}")
        self.layer, self.unit = module.LAYER, module.UNIT
        self.better, self.source = module.BETTER, module.SOURCE
        self.moves = module.MOVES
        self._read = module.read

    def read(self, run):
        v = self._read(run)
        return None if v is None else float(v)


def metric_readers(root: Path = BENCH) -> list[MetricReader]:
    """Every ``metrics/<name>.py``, loaded by file path (names may hold
    dots), in name order."""
    out = []
    for path in sorted((root / "metrics").glob("*.py")):
        name = path.name[:-3]
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{len(out)}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        out.append(MetricReader(name, module))
    return out

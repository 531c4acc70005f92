"""Cells, configurations and per-layer metrics are found by name: adding
one is adding files."""
import json
import shutil

import pytest

from conftest import BENCH
from harness.spec import SpecError, load_cell, metric_readers

MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def test_a_new_cell_and_metric_are_found_without_code_changes(tmp_path):
    for d in ("configs", "workloads", "metrics"):
        shutil.copytree(BENCH / d, tmp_path / d)
    cfg = json.loads((tmp_path / "configs" / "pen-8x2.json").read_text())
    cfg["field"]["depth"] = 10
    (tmp_path / "configs" / "pen-10x2.json").write_text(json.dumps(cfg))
    (tmp_path / "workloads" / "pen-10x2.full.open.json").write_text(
        json.dumps({"config": "pen-10x2", "loop": "open", "threshold": 1.1,
                    "rate_per_s": 1000, "limits": {}}))
    (tmp_path / "metrics" / "steps.open.py").write_text(
        "LAYER = 'scheduler'\nUNIT = 'steps'\nBETTER = 'higher'\n"
        "SOURCE = 'program_counter'\nMOVES = 'p50_ms'\n"
        "def read(run):\n    return run.window.steps\n")
    work, cfg = load_cell("pen-10x2.full.open", root=tmp_path)
    assert cfg["field"]["depth"] == 10 and work["threshold"] == 1.1
    names = [m.name for m in metric_readers(tmp_path)]
    assert "steps.open" in names and len(names) == len(
        metric_readers()) + 1


def test_a_missing_cell_is_refused():
    with pytest.raises(SpecError):
        load_cell("no-such-cell")


def test_manifest_matches_the_files():
    readers = {m.name: m for m in metric_readers()}
    for c in MANIFEST["configs"]:
        assert (BENCH.parent / c["file"]).is_file()
    names = {w["name"] for w in MANIFEST["workloads"]}
    for w in MANIFEST["workloads"]:
        work, cfg = load_cell(w["name"])
        assert work["config"] == w["config"]
        assert cfg["chips"] == w["chips"]
    for m in MANIFEST["per_layer"]:
        r = readers[m["name"]]
        assert (r.layer, r.unit, r.better, r.source, r.moves) == (
            m["layer"], m["unit"], m["better"], m["source"], m["moves"])
        assert set(m.get("workloads", ())) <= names
    assert set(readers) == {m["name"] for m in MANIFEST["per_layer"]}

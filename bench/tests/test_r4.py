"""The four-replica cell at tiny size on four forced XLA host devices (a
subprocess: the device count is fixed when jax first initializes), and
the two readers of its per-layer metrics on made-up runs."""
import json
import os
import subprocess
import sys
import types

import pytest

from conftest import BENCH
from harness.spec import metric_readers

_SCRIPT = r"""
import json, sys
from pathlib import Path

import numpy as np

sys.path[:0] = [sys.argv[1], sys.argv[1] + "/tests"]
import harness.cell as cell
from tiny import run_tiny

shares = []
sample = cell._sample


def noting(served, size, seed):
    idx = sample(served, size, seed)
    shares.append(np.bincount(served["replica"][idx], minlength=4).tolist())
    return idx


cell._sample = noting
out = {}
for control in (False, True):
    # the configuration's 16 trees at depth 8 over the published test
    # rows: at the tiny default size the control changes no answer
    r = run_tiny(Path(sys.argv[2]), "pen-8x2-r4.gate.open", chips=4,
                 control=control, rate_per_s=3000, check_sample=3000,
                 dataset={"n_test": 3498},
                 field={"n_trees": 16, "depth": 8})
    out["control" if control else "sound"] = {
        "checks": {k: v for k, (v, _) in r["checks"].items()},
        "compiles": r["compiles_in_window"], "shares": shares[-1]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(BENCH.parent / "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(BENCH),
         str(tmp_path_factory.mktemp("r4"))],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_replicas_are_correct_and_share_the_sample(four):
    sound = four["sound"]
    assert all(v == 0 for v in sound["checks"].values()), sound
    assert sound["compiles"] == 0
    assert len(sound["shares"]) == 4 and min(sound["shares"]) > 0


def test_four_replica_control_is_not_correct(four):
    offedge = {k: v for k, v in four["control"]["checks"].items()
               if k.startswith("offedge_")}
    assert offedge and sum(offedge.values()) > 0, four["control"]


READERS = {m.name: m for m in metric_readers()}


def _run(n_chips=4, busy=(1.0, 0.8, 0.9, 0.5), route=800, loop="open"):
    phase = {"dispatch": 6000, "harvest": 2000, "refill": 3000}
    if route is not None:
        phase["route"] = route
    return types.SimpleNamespace(
        loop=loop, n_chips=n_chips,
        window=types.SimpleNamespace(phase_ns=phase, steps=4),
        trace=types.SimpleNamespace(busy_s=list(busy)))


def test_r4_readers():
    bal = READERS["chip_busy_balance.r4"]
    route = READERS["route_us_per_step.r4"]
    r = _run()
    assert bal.read(r) == pytest.approx(50.0)
    assert route.read(r) == pytest.approx(0.2)
    # chips with no operation in the window are absent from the trace
    assert bal.read(_run(busy=(0.5,))) == 0.0
    # a program without the route counter gives nothing for it
    assert route.read(_run(route=None)) is None
    assert route.read(_run(loop="closed")) is None
    for m in (bal, route):
        assert m.read(_run(n_chips=1, busy=(1.0,))) is None
        assert (m.layer, m.moves) in {("router", "p50_ms"),
                                      ("scheduler", "p50_ms")}

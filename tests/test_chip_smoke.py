"""chip_smoke.py's phases at tiny sizes on the CPU (Pallas interpreted),
and its refusal to run without a TPU."""
import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.data import make_dataset

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny(name, n_train=300, n_test=64):
    ds = make_dataset(name)
    return dataclasses.replace(ds, x_train=ds.x_train[:n_train],
                               y_train=ds.y_train[:n_train],
                               x_test=ds.x_test[:n_test],
                               y_test=ds.y_test[:n_test])


@pytest.fixture(scope="module")
def trained(smoke, tmp_path_factory):
    ds = _tiny("penbased")
    clf = smoke.phase_train(ds, n_trees=4, grove_size=2, depth=4)
    root = tmp_path_factory.mktemp("smoke") / "registry"
    registry, packs, tenants = smoke.phase_pack_publish(clf, root)
    return ds, clf, registry, packs, tenants


def test_train_and_publish(trained, capsys):
    ds, clf, registry, packs, tenants = trained
    assert clf.score(ds.x_test, ds.y_test) > 0.5
    assert set(packs) == {"fp32", "int8"}
    for p, tenant in tenants.items():
        assert registry.live_version(tenant) == 1
        assert registry.load(tenant)[0].precision == p


def test_serve_completes_every_request(smoke, trained, capsys):
    ds, _, registry, _, tenants = trained
    s = smoke.phase_serve(registry, tenants, ds, n_requests=48, n_slots=16)
    assert sum(n for n, _, _ in s.values()) == 48
    assert all(1.0 <= hops <= 2.0 for _, _, hops in s.values())
    assert "[serve] completed=48/48" in capsys.readouterr().out


def test_check_phase_fused_matches_reference(smoke, trained, capsys):
    ds, _, _, packs, _ = trained
    smoke.phase_check(packs, ds)
    out = capsys.readouterr().out
    assert out.count("[check] fused vs reference") == 2


def test_wide_phase(smoke, capsys):
    smoke.phase_wide(_tiny("isolet", n_test=32), n_trees=2, depth=5)
    out = capsys.readouterr().out
    assert "[wide] isolet trainer=host" in out
    assert out.count("[wide] fused vs reference") == 2


def test_check_agreement_flags_off_gate_disagreement(smoke):
    """A label flip far from the gate is a failure; one on the gate is
    tolerated."""
    from repro.core.engine import FogResult

    def res(p, hops):
        p = np.asarray(p, np.float32)
        return FogResult(proba=p, label=p.argmax(-1), hops=np.asarray(hops))

    base = [[0.9, 0.1]] * 400
    want = res(base, [1] * 400)
    on_edge = res([[0.65, 0.35]] + base[1:], [1] * 400)
    smoke.check_agreement(on_edge, res([[0.65, 0.35]] + base[1:], [2] + [1] * 399),
                          0.3, 4)
    with pytest.raises(AssertionError):
        smoke.check_agreement(res([[0.1, 0.9]] + base[1:], [1] * 400), want,
                              0.3, 4)


def test_entry_point_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_refuses_outside_the_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "sources are missing" in proc.stderr


_CROSS_CHIP = r"""
import dataclasses, sys
sys.path.insert(0, sys.argv[1])
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[2])
smoke = importlib.util.module_from_spec(spec); spec.loader.exec_module(smoke)
from repro.data import make_dataset
ds = make_dataset("penbased")
ds = dataclasses.replace(ds, x_train=ds.x_train[:300], y_train=ds.y_train[:300],
                         x_test=ds.x_test[:64], y_test=ds.y_test[:64])
clf = smoke.phase_train(ds, n_trees=8, grove_size=2, depth=4, tag="setup")
registry, packs, tenants = smoke.phase_pack_publish(clf, smoke.Path(sys.argv[3]))
smoke.phase_replicas(registry, tenants, ds, n_chips=4, n_requests=64,
                     n_slots=32)
smoke.phase_ring(packs, ds, n_chips=4)
print("CROSS_CHIP_OK")
"""


def test_cross_chip_phases_on_four_host_devices(tmp_path):
    """The --chips 4 phases on four forced host devices: replicas land on
    their own devices, and the ring agrees with the reference."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", _CROSS_CHIP, str(ROOT / "src"),
         str(ROOT / "chip_smoke.py"), str(tmp_path / "registry")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = proc.stdout
    assert "CROSS_CHIP_OK" in out
    assert out.count("[ring] ring vs reference") == 2
    placement = [ln for ln in out.splitlines()
                 if ln.startswith("[replicas] placement")]
    assert placement
    for i in range(4):
        assert f"r{i}@{i}:" in placement[0]
        assert f"'x': [{i}]" in placement[0].split(f"r{i}@{i}:")[1]

"""The packed dispatch starts each output's device-to-host copy at enqueue
(``DeviceDispatcher.dispatch_packed``).  A pipelined packed batcher with
``trace`` on counts every harvested output as prefetched, and serves
exactly what it serves with the prefetch turned into a no-op — on one and
on four forced XLA host devices (a subprocess each: the device count is
fixed when jax first initializes)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

_SCRIPT = r"""
import json, sys
from unittest import mock

import jax.numpy as jnp
import numpy as np

from repro.core import FogPolicy, split
from repro.data import make_dataset
from repro.forest import TrainConfig, train_random_forest
from repro.launch.mesh import serve_devices
from repro.serve.dispatch import DeviceDispatcher, ForestReplicaServer
from repro.serve.scheduler import ContinuousBatcher, Request

n_dev = int(sys.argv[1])
ds = make_dataset("penbased")
rf = train_random_forest(ds.x_train, ds.y_train, ds.n_classes,
                         TrainConfig(n_trees=8, max_depth=5, seed=1))


def serve():
    server = ForestReplicaServer(split(rf, 2), ds.x_test.shape[1],
                                 backend="fused",
                                 precisions=("fp32", "int8"), seed=0)
    disp = DeviceDispatcher(server.packed_factory, serve_devices(n_dev))
    b = ContinuousBatcher(
        8 * n_dev, None, server.prefill, eos_id=-1,
        default_policy=FogPolicy(threshold=0.7, precision="fp32"),
        dispatcher=disp, pipeline=True)
    energy = {}
    harvest = disp.harvest_packed

    def noting(n_slots, counts=None):
        # each occupied lane's request and the energy harvested for it
        occ = b._inflight_occ
        rids = [b.slots[i].request.rid for i in occ]
        out = harvest(n_slots, counts=counts)
        energy.update(zip(rids, out[2][occ].tolist()))
        return out

    disp.harvest_packed = noting
    b.trace = True
    rng = np.random.default_rng(7)
    rid = 0
    try:
        for _ in range(4):
            for _ in range(int(rng.integers(3, 12 * n_dev))):
                b.submit(Request(
                    rid=rid, prompt=ds.x_test[rng.integers(len(ds.x_test))],
                    max_new_tokens=1, policy=FogPolicy(
                        threshold=float(rng.uniform(0.3, 0.9)),
                        precision="int8" if rng.random() < 0.5
                        else "fp32")))
                rid += 1
            for _ in range(2):
                b.step()
        while b.queue or b.active:
            b.step()
        b.flush()
    finally:
        b.trace = False
    served = {r.rid: [r.generated[0], r.hops[0], energy[r.rid]]
              for r in b.completed}
    assert len(served) == rid
    return {"counts": b.counts, "served": served}


array = type(jnp.zeros(1))
real, calls = array.copy_to_host_async, [0]


def counted(self):
    calls[0] += 1
    return real(self)


with mock.patch.object(array, "copy_to_host_async", counted):
    prefetched = serve()
prefetched["calls"] = calls[0]
with mock.patch.object(array, "copy_to_host_async", lambda self: None):
    skipped = serve()
print(json.dumps({"prefetched": prefetched, "skipped": skipped}))
"""


@pytest.mark.parametrize("n_dev", [1, 4])
def test_prefetch_counts_and_serves_as_without(n_dev):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["JAX_PLATFORMS"] = "cpu"
    repo = Path(__file__).resolve().parent.parent
    env["PYTHONPATH"] = str(repo / "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT, str(n_dev)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    on, off = out["prefetched"], out["skipped"]
    c = on["counts"]
    assert c["host_copies_prefetched"] == c["host_copies"] > 0
    # one copy started for each output harvested, three per dispatch
    assert on["calls"] == c["host_copies"]
    assert c["host_copies"] % 3 == 0
    assert on["served"] == off["served"]
    for k in ("lane_hops_walked", "lane_hops_served"):
        assert on["counts"][k] == off["counts"][k] > 0

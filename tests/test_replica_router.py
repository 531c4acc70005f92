"""The scheduler's replica router (``ContinuousBatcher._route``).

Each refill burst goes whole to one replica: the one with the most free
slots, ties in turn after the replica used last, spilling into the next
replica in turn only when it is full.  With one replica that is the single
free-slot heap of before, pinned here against a copy of the old rule.  On
four forced XLA host devices (a subprocess: the device count is fixed when
jax first initializes) the bursts take turns, a full replica spills, and
every request served through four replicas equals the plain reference
evaluated from the start grove its replica drew for that dispatch.
"""
import heapq
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import FogPolicy, split
from repro.launch.mesh import serve_devices
from repro.serve.dispatch import DeviceDispatcher, ForestReplicaServer
from repro.serve.scheduler import ContinuousBatcher, Request


def _schedule(seed=3, waves=12):
    """Bursts of requests (rid, max_new_tokens) and the steps after each."""
    rng = np.random.default_rng(seed)
    rid, out = 0, []
    for _ in range(waves):
        burst = []
        for _ in range(int(rng.integers(1, 14))):
            burst.append((rid, int(rng.integers(1, 4))))
            rid += 1
        out.append((burst, int(rng.integers(1, 3))))
    return out


def _old_rule(n_slots, schedule):
    """The placement before the router: one min-heap of free slots over
    every slot; each step refills lowest first, then every occupied slot
    takes one token and the finished ones go back on the heap."""
    free = list(range(n_slots))
    queue, left, placed = [], {}, []
    work = [(burst, steps) for burst, steps in schedule]
    work.append(([], 10**3))
    for burst, steps in work:
        queue.extend(burst)
        for _ in range(steps):
            while queue and free:
                rid, n_tok = queue.pop(0)
                i = heapq.heappop(free)
                placed.append((rid, i))
                left[i] = n_tok
            for i in list(left):
                left[i] -= 1
                if not left[i]:
                    del left[i]
                    heapq.heappush(free, i)
            if not queue and not left:
                break
    return placed


@pytest.mark.parametrize("path", ["packed", "host"])
def test_one_replica_places_as_the_old_heap(trained, path):
    ds, rf = trained
    n_slots = 8
    placed = []
    if path == "packed":
        server = ForestReplicaServer(split(rf, 2), ds.x_test.shape[1],
                                     backend="fused", seed=0)
        disp = DeviceDispatcher(server.packed_factory, serve_devices(1))
        b = ContinuousBatcher(n_slots, None, server.prefill, eos_id=-1,
                              default_policy=FogPolicy(threshold=0.7),
                              dispatcher=disp, pipeline=True)
        admit = disp.admit_lanes

        def noting(lanes, rows, thr, bud):
            placed.extend((b.slots[i].request.rid, int(i)) for i in lanes)
            return admit(lanes, rows, thr, bud)

        disp.admit_lanes = noting
    else:
        def prefill(slot, prompt):
            placed.append((int(prompt[0]), slot))
            return 1

        def decode(tokens, lengths, policy):
            return (jax.numpy.ones((n_slots, 4), jax.numpy.float32),
                    jax.numpy.ones((n_slots,), jax.numpy.int32))

        b = ContinuousBatcher(n_slots, decode, prefill, eos_id=-1)
    schedule = _schedule()
    for burst, steps in schedule:
        for rid, n_tok in burst:
            prompt = (ds.x_test[rid] if path == "packed"
                      else np.asarray([rid]))
            b.submit(Request(rid=rid, prompt=prompt, max_new_tokens=n_tok))
        for _ in range(steps):
            b.step()
    while b.queue or b.active:
        b.step()
    b.flush()
    assert len(b.completed) == sum(len(bu) for bu, _ in schedule)
    assert placed == _old_rule(n_slots, schedule)


_SCRIPT = r"""
import json

import jax
import numpy as np

from repro.core import FogPolicy, split
from repro.core.engine import _eval_core
from repro.core.policy import NO_BUDGET
from repro.data import make_dataset
from repro.forest import ForestPack, TrainConfig, train_random_forest
from repro.launch.mesh import serve_devices
from repro.serve.dispatch import DeviceDispatcher, ForestReplicaServer
from repro.serve.scheduler import ContinuousBatcher, Request

SPAN = 32
SEED = 5
ds = make_dataset("penbased")
rf = train_random_forest(ds.x_train, ds.y_train, ds.n_classes,
                         TrainConfig(n_trees=8, max_depth=5, seed=1))
gc = split(rf, 2)
F = ds.x_test.shape[1]
out = {}


def plane(n_dev, thresh, span=SPAN):
    server = ForestReplicaServer(gc, F, backend="fused",
                                 precisions=("fp32", "int8"), seed=SEED)
    disp = DeviceDispatcher(server.packed_factory, serve_devices(n_dev))
    b = ContinuousBatcher(span * n_dev, None, server.prefill, eos_id=-1,
                          default_policy=FogPolicy(threshold=thresh),
                          dispatcher=disp, pipeline=True)
    bursts = []
    admit = disp.admit_lanes

    def noting(lanes, rows, thr, bud):
        bursts.append([int(i) for i in lanes])
        return admit(lanes, rows, thr, bud)

    disp.admit_lanes = noting
    return b, bursts


def request(rid, n_tok=1, thresh=0.7):
    prec = "int8" if rid % 2 else "fp32"
    return Request(rid=rid, prompt=ds.x_test[rid % len(ds.x_test)],
                   max_new_tokens=n_tok,
                   policy=FogPolicy(threshold=thresh, precision=prec))


# turns: 40 bursts of 10, each served before the next arrives
b, bursts = plane(4, 0.7, span=128)
touched = []
for k in range(40):
    for j in range(10):
        b.submit(request(10 * k + j))
    b.step()
    b.step()
    touched.append(sorted({p.device for p in b.last_dispatches}))
b.flush()
out["turns"] = {"bursts": [sorted({i // 128 for i in bu}) for bu in bursts],
                "touched": touched, "routed": b.counts,
                "done": len(b.completed)}

# spill: requests that stay in their slots fill each replica to 24 of 32
b, bursts = plane(4, 0.7)
b.trace = True
rid = 0
for n in (24, 24, 24, 24, 20, 3):
    for _ in range(n):
        b.submit(request(rid, n_tok=10**6))
        rid += 1
    b.step()
b.trace = False
out["spill"] = {"bursts": bursts,
                "routed": [b.counts[f"routed_{d}"] for d in range(4)]}

# end to end: open-loop arrivals, every answer against the reference
rng = np.random.default_rng(11)
b, _ = plane(4, 0.7)
disp = b.dispatcher
where, count = {}, {}
dispatch = disp.dispatch_packed


def recording(lanes, *a, **kw):
    pend = dispatch(lanes, *a, **kw)
    for p in pend:
        count[p.device] = count.get(p.device, 0) + 1
        for lane, loc in zip(p.lanes.tolist(), p.local.tolist()):
            where[b.slots[lane].request.rid] = (p.device, count[p.device],
                                                loc)
    return pend


disp.dispatch_packed = recording
rid = 0
for _ in range(60):
    for _ in range(int(rng.poisson(12))):
        b.submit(request(rid))
        rid += 1
    b.step()
while b.queue or b.active:
    b.step()
b.flush()
G = gc.n_groves
packs = {p: ForestPack.from_groves(gc, p) for p in ("fp32", "int8")}
draws = {}
bad, reps = 0, []
for r in b.completed:
    dev, k, loc = where[r.rid]
    if (dev, k) not in draws:
        key = jax.random.fold_in(jax.random.key(SEED + dev), k)
        draws[dev, k] = np.asarray(jax.random.randint(key, (SPAN,), 0, G))
    res = _eval_core(packs[r.policy.precision], r.prompt[None],
                     draws[dev, k][loc:loc + 1], 0.7, NO_BUDGET, G,
                     "reference", 256, False)
    want = (int(res.label[0]), int(res.hops[0]))
    bad += want != (r.generated[0], r.hops[0])
    reps.append(dev)
out["reference"] = {"n": len(b.completed), "submitted": rid, "bad": bad,
                    "per_replica": np.bincount(reps, minlength=4).tolist()}

# gate 1.1: every lane walks every grove; labels as on one chip
served = {}
for n_dev in (4, 1):
    b, _ = plane(n_dev, 1.1, span=SPAN * 4 // n_dev)
    for k in range(8):
        for j in range(k * 17, (k + 1) * 17):
            b.submit(request(j, thresh=1.1))
        b.step()
    while b.queue or b.active:
        b.step()
    b.flush()
    served[n_dev] = {r.rid: (r.generated[0], r.hops[0]) for r in b.completed}
out["full"] = {"n": len(served[4]), "G": G,
               "labels_equal": all(served[4][k][0] == served[1][k][0]
                                   for k in served[1]),
               "hops": sorted({h for _, h in served[4].values()})}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    repo = Path(__file__).resolve().parent.parent
    env["PYTHONPATH"] = str(repo / "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_bursts_take_turns_over_four_replicas(four):
    t = four["turns"]
    assert t["done"] == 400
    assert all(len(reps) == 1 for reps in t["bursts"])
    assert [sum(reps == [d] for reps in t["bursts"])
            for d in range(4)] == [10] * 4
    # in turn: 0, 1, 2, 3, 0, ...
    assert [reps[0] for reps in t["bursts"]] == [k % 4 for k in range(40)]
    # each burst's dispatch ran on the one replica it went to
    assert t["touched"] == [[k % 4] for k in range(40)]


def test_a_full_replica_spills_to_the_next_in_turn(four):
    s = four["spill"]
    span = 32
    first = [list(range(d * span, d * span + 24)) for d in range(4)]
    assert s["bursts"][:4] == first
    # 20 more: replica 0 (ties, in turn after 3) has 8, then 1 has 8,
    # then 2 gives 4
    assert s["bursts"][4] == (list(range(24, 32)) + list(range(56, 64))
                              + list(range(88, 92)))
    # 3 more: replica 3 has the most free slots
    assert s["bursts"][5] == [120, 121, 122]
    assert s["routed"] == [32, 32, 28, 27]


def test_four_replicas_serve_as_the_reference(four):
    r = four["reference"]
    assert r["n"] == r["submitted"] > 500
    assert r["bad"] == 0
    assert min(r["per_replica"]) >= 0.15 * r["n"]


def test_full_walk_labels_match_one_chip(four):
    f = four["full"]
    assert f["n"] == 8 * 17
    assert f["labels_equal"]
    assert f["hops"] == [f["G"]]

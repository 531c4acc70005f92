"""Data-parallel serving plane (serve/dispatch.py).

In-process tests drive a DeviceDispatcher over N logical replicas (which
may share the host's single physical CPU device — the routing/scatter
contract is device-count-agnostic) through the packed replica protocol:
host mock decoders behind :class:`HostReplica`, and the forest's
``packed_factory``.  The subprocess test forces 4 real XLA host devices
via ``XLA_FLAGS=--xla_force_host_platform_device_count`` and asserts each
replica's outputs were actually computed on its own device.
"""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import NO_BUDGET, EnergyModel, FogPolicy, split
from repro.serve.dispatch import (DeviceDispatcher, ForestReplicaServer,
                                  HostReplica, host_replica_factory,
                                  replicate)
from repro.serve.scheduler import ContinuousBatcher, Request


def _stamp_factory(calls=None, price=None):
    """Mock host replica: logits one-hot on the replica index (argmax ==
    which device served the lane), hops = index + 1, and an optional record
    of (index, thresholds, budgets, precision) per decode call."""
    def factory(index, device, span):
        def decode(tokens, lengths, policy):
            if calls is not None:
                calls.append((index,
                              np.array(policy.threshold, np.float32,
                                       copy=True),
                              np.array(policy.lane_budgets(span), np.int32,
                                       copy=True),
                              policy.precision))
            logits = np.zeros((span, 8), np.float32)
            logits[:, index] = 1.0
            hops = np.full((span,), index + 1)
            return jnp.asarray(logits), jnp.asarray(hops)
        return HostReplica(decode, lambda lane, prompt: len(prompt), span,
                           index=index, device=device, price=price)
    return factory



def _four_replicas():
    dev = jax.devices()[0]
    return [dev] * 4


def test_replicate_puts_one_copy_per_device():
    devs = _four_replicas()
    copies = replicate({"w": jnp.arange(3)}, devs)
    assert len(copies) == 4
    for c in copies:
        assert next(iter(c["w"].devices())) == devs[0]


def test_bind_span_and_rebind_rules():
    disp = DeviceDispatcher(_stamp_factory(), _four_replicas())
    with pytest.raises(ValueError, match="divide evenly"):
        disp.bind(10)
    disp.bind(8)
    assert disp.span == 2
    disp.bind(8)                       # idempotent
    with pytest.raises(ValueError, match="cannot rebind"):
        disp.bind(16)
    assert disp.device_of(0) == 0 and disp.device_of(7) == 3
    np.testing.assert_array_equal(disp.lane_devices([0, 3, 6]), [0, 1, 3])


def test_dispatch_routes_only_intersecting_devices():
    calls = []
    disp = DeviceDispatcher(_stamp_factory(calls), _four_replicas())
    disp.bind(8)
    lanes = np.asarray([0, 1, 5])
    disp.admit_lanes(lanes, [np.asarray([3])] * 3, np.full(3, 0.5),
                     np.full(3, NO_BUDGET))
    pend = disp.dispatch_packed(lanes, 0.5, NO_BUDGET)
    # lanes 0,1 -> device 0; lane 5 -> device 2; devices 1,3 untouched
    assert sorted(p.device for p in pend) == [0, 2]
    assert sorted(c[0] for c in calls) == [0, 2]
    nxt, hops, energy, drained = disp.harvest_packed(8)
    assert all(isinstance(a, np.ndarray) for a in (nxt, hops, energy))
    assert len(drained) == 2
    # only the bucket's lanes are scattered; untouched lanes stay zero
    np.testing.assert_array_equal(nxt[[0, 1, 5]], [0, 0, 2])
    assert hops[0] == 1 and hops[5] == 3 and hops[2] == 0
    # no price given: the served lanes are unpriced, the rest untouched
    assert np.isnan(energy[[0, 1, 5]]).all() and energy[2] == 0


def test_per_lane_policy_vectors_sliced_per_span():
    calls = []
    disp = DeviceDispatcher(_stamp_factory(calls), _four_replicas())
    disp.bind(8)
    thr = np.linspace(0.1, 0.8, 8, dtype=np.float32)
    bud = np.arange(1, 9, dtype=np.int32)
    lanes = np.arange(8)
    disp.admit_lanes(lanes, [np.asarray([3])] * 8, thr, bud)
    disp.dispatch_packed(lanes, 0.5, NO_BUDGET)
    disp.harvest_packed(8)
    assert len(calls) == 4
    for index, seen_thr, seen_bud, _ in calls:
        np.testing.assert_allclose(seen_thr, thr[2 * index:2 * index + 2])
        np.testing.assert_array_equal(seen_bud,
                                      bud[2 * index:2 * index + 2])


def test_harvest_without_dispatch_raises():
    disp = DeviceDispatcher(_stamp_factory(), _four_replicas())
    disp.bind(8)
    with pytest.raises(ValueError, match="nothing dispatched"):
        disp.harvest_packed(8)


def test_batcher_dispatch_mode_groups_precisions_across_devices():
    """Three precision groups in one step: each group dispatches once per
    intersecting device, and every lane harvests logits/hops from its OWN
    group's replica call."""
    calls = []
    disp = DeviceDispatcher(_stamp_factory(calls), _four_replicas())
    b = ContinuousBatcher(8, None, lambda slot, prompt: len(prompt),
                          eos_id=-1, default_policy=FogPolicy(threshold=0.5),
                          dispatcher=disp)
    precs = [None, "int8", "bf16", None, "int8", "bf16", None, None]
    for rid, p in enumerate(precs):
        pol = None if p is None else FogPolicy(threshold=0.5, precision=p)
        b.submit(Request(rid=rid, prompt=np.asarray([3]), max_new_tokens=1,
                         policy=pol))
    b.step()
    assert len(b.completed) == 8
    # span=2: None lanes {0,3,6,7} -> devices {0,1,3}; int8 {1,4} ->
    # {0,2}; bf16 {2,5} -> {1,2} — one call per (group, touched device)
    by_prec = {}
    for *_, prec in calls:
        by_prec[prec] = by_prec.get(prec, 0) + 1
    assert by_prec == {None: 3, "int8": 2, "bf16": 2}
    # harvest attribution: lane i is served by device i // span
    for r in b.completed:
        assert r.generated == [r.rid // 2]
        assert r.hops == [r.rid // 2 + 1]
    devs = {p.device for p in b.last_dispatches}
    assert devs == {0, 1, 2, 3}


def test_empty_lane_none_group_folds_into_real_group():
    """Empty lanes belong to no serving bucket: the batcher spends no
    dispatch on them — only the buckets holding lanes are dispatched, and
    only on the replicas those lanes touch."""
    calls = []
    disp = DeviceDispatcher(_stamp_factory(calls), [jax.devices()[0]] * 2)
    b = ContinuousBatcher(4, None, lambda slot, prompt: len(prompt),
                          eos_id=-1, dispatcher=disp)
    for rid in range(2):
        b.submit(Request(rid=rid, prompt=np.asarray([1]), max_new_tokens=1,
                         policy=FogPolicy(precision="int8")))
    b.step()
    # the burst went whole to replica 0 (slots 0,1, int8); slots 2,3 are
    # empty: one call, on replica 0 only
    assert [(c[0], c[-1]) for c in calls] == [(0, "int8")]
    assert len(b.completed) == 2


def test_forest_replica_server_end_to_end(trained):
    """The paper's serving workload against logical replicas: every request
    classified, hop telemetry positive, predictions match the plain
    single-program forest evaluation's quality."""
    ds, rf = trained
    gc = split(rf, 2)
    server = ForestReplicaServer(gc, ds.x_test.shape[1], backend="fused",
                                 precisions=("fp32", "int8"))
    devs = [jax.devices()[0]] * 2
    disp = DeviceDispatcher(server.packed_factory, devs)
    n = 32
    b = ContinuousBatcher(n, None, server.prefill, eos_id=-1,
                          default_policy=FogPolicy(threshold=0.7),
                          dispatcher=disp)
    rows = ds.x_test[:n]
    labels = ds.y_test[:n]
    for rid in range(n):
        pol = (FogPolicy(threshold=0.7, precision="int8") if rid % 4 == 0
               else None)
        b.submit(Request(rid=rid, prompt=rows[rid], max_new_tokens=1,
                         policy=pol))
    done = b.run()
    assert len(done) == n
    preds = np.array([r.generated[0] for r in sorted(done,
                                                     key=lambda r: r.rid)])
    acc = float((preds == labels).mean())
    assert acc > 0.7                    # forest-quality, not token noise
    assert all(r.hops[0] >= 1 for r in done)
    # both replicas served their own spans
    assert {p.device for p in b.last_dispatches} <= {0, 1}


def test_forest_replica_server_validates_rows(trained):
    ds, rf = trained
    server = ForestReplicaServer(split(rf, 2), ds.x_test.shape[1])
    with pytest.raises(ValueError, match="not bound"):
        server.prefill(0, ds.x_test[0])
    disp = DeviceDispatcher(server.packed_factory, [jax.devices()[0]])
    disp.bind(4)
    with pytest.raises(ValueError, match="features"):
        server.prefill(0, ds.x_test[0][:3])


def test_span_upload_is_a_copy_reused_by_the_step_s_second_call(
        trained, monkeypatch):
    """The forest replica uploads its staged span once per change: a
    second bucket call in the same step reuses the upload, and a write
    to the staging buffer while a call is in flight (an admit, a prefill)
    reaches neither that call's input nor its outputs.  ``device_put`` is
    handed a private copy of the staging buffer, so even a backend that
    aliases host arrays (XLA's CPU client does, for 64-byte aligned ones)
    cannot see a later write."""
    ds, rf = trained
    gc = split(rf, 2)
    F = ds.x_test.shape[1]
    n, k = 512, 300

    def plane():
        server = ForestReplicaServer(gc, F, backend="fused",
                                     precisions=("fp32", "int8"), seed=2)
        disp = DeviceDispatcher(server.packed_factory, [jax.devices()[0]])
        disp.bind(n)
        return server, disp

    lanes = np.arange(k)
    knobs = (np.full(k, 0.7, np.float32), np.full(k, NO_BUDGET, np.int32))
    server, disp = plane()
    rep = disp.replicas[0]
    disp.admit_lanes(lanes, list(ds.x_test[:k]), *knobs)
    handed, put = [], jax.device_put

    def noting_put(x, *a, **kw):
        handed.append(x)
        return put(x, *a, **kw)

    with monkeypatch.context() as m:
        m.setattr(jax, "device_put", noting_put)
        first = disp.dispatch_packed(lanes, 0.7, NO_BUDGET, precision="fp32")
    [host] = [x for x in handed if np.shape(x) == rep.stage.shape]
    assert not np.shares_memory(host, rep.stage)
    upload, staged = rep.state["upload"], rep.stage.copy()
    second = disp.dispatch_packed(lanes, 0.7, NO_BUDGET, precision="int8")
    assert first[0].uploaded == n * (F + 2) * 4 == staged.nbytes
    assert second[0].uploaded == 0 and rep.state["upload"] is upload
    # rewrite every staged lane before the calls are harvested
    disp.admit_lanes(lanes, list(ds.x_test[k:2 * k]), *knobs)
    server.prefill(k, ds.x_test[0])
    assert rep.state["upload"] is None
    assert not np.array_equal(rep.stage, staged)
    np.testing.assert_array_equal(np.asarray(upload), staged)
    counts = dict.fromkeys(("lane_hops_walked", "lane_hops_served",
                            "host_copies", "host_copies_prefetched",
                            "span_uploads", "span_upload_bytes"), 0)
    got = disp.harvest_packed(n, counts=counts)
    assert (counts["span_uploads"], counts["span_upload_bytes"]) == (
        1, staged.nbytes)
    # the same two calls on a plane whose staging nobody rewrote
    _, ref = plane()
    ref.admit_lanes(lanes, list(ds.x_test[:k]), *knobs)
    for prec in ("fp32", "int8"):
        ref.dispatch_packed(lanes, 0.7, NO_BUDGET, precision=prec)
    want = ref.harvest_packed(n)
    for a, b in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(a, b)
    # the rewrite is what the next call evaluates: a fresh upload
    nxt = disp.dispatch_packed(lanes, 0.7, NO_BUDGET, precision="fp32")
    assert nxt[0].uploaded == staged.nbytes
    np.testing.assert_array_equal(np.asarray(rep.state["upload"]), rep.stage)
    disp.harvest_packed(n)


def test_forest_replica_server_energy_models(trained):
    ds, rf = trained
    server = ForestReplicaServer(split(rf, 2), ds.x_test.shape[1],
                                 precisions=("fp32", "int8"))
    m32 = server.energy_model("fp32")
    m8 = server.energy_model("int8")
    assert server.energy_model() is m32            # default + cached
    hops = np.full(8, 3)
    assert float(np.asarray(m8.lane_pj(hops)).sum()) < float(
        np.asarray(m32.lane_pj(hops)).sum())


_SUBPROC = r"""
import jax, numpy as np
import jax.numpy as jnp
from repro.core import NO_BUDGET
from repro.launch.mesh import serve_devices
from repro.serve.dispatch import DeviceDispatcher, HostReplica

devs = serve_devices(4)
assert len({d.id for d in devs}) == 4
seen = {}

def factory(index, device, span):
    def decode(tokens, lengths, policy):
        seen[index] = next(iter(tokens.devices()))
        # the token each lane was admitted with decides its label
        logits = jax.nn.one_hot(tokens, 16)
        hops = jnp.full((span,), index + 1)
        return logits, hops
    return HostReplica(decode, lambda lane, prompt: 1, span, index=index,
                       device=device)

disp = DeviceDispatcher(factory, devs)
disp.bind(16)
lanes = np.arange(16)
disp.admit_lanes(lanes, [np.asarray([15 - i]) for i in lanes],
                 np.full(16, 0.5, np.float32), np.full(16, NO_BUDGET))
disp.dispatch_packed(lanes, 0.5, NO_BUDGET)
nxt, hops, energy, pend = disp.harvest_packed(16)
assert sorted({p.device for p in pend}) == [0, 1, 2, 3]
for p in pend:
    for out in (p.nxt, p.hops, p.energy):
        assert next(iter(out.devices())) == devs[p.device]
    assert seen[p.device] == devs[p.device]
np.testing.assert_array_equal(nxt, 15 - np.arange(16))
np.testing.assert_array_equal(hops, np.repeat([1, 2, 3, 4], 4))
print("MULTIDEV-OK")
"""


def test_real_four_device_dispatch_subprocess():
    """The real thing: 4 forced XLA host devices, each replica's outputs
    computed (and verified resident) on its own device."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    repo = Path(__file__).resolve().parent.parent
    env["PYTHONPATH"] = str(repo / "src")
    proc = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "MULTIDEV-OK" in proc.stdout


# ---------------------------------------------------------------------------
# HostReplica: host decode callables behind the packed protocol
# ---------------------------------------------------------------------------

def _precision_hops_decode(tokens, lengths, policy, bucket=None):
    """hops 3 at int8, 5 otherwise; the next token is the lane's + 1."""
    n = np.asarray(tokens).shape[0]
    logits = np.zeros((n, 64), np.float32)
    logits[np.arange(n), (np.asarray(tokens) + 1) % 64] = 1.0
    return jnp.asarray(logits), jnp.full((n,), 3 if policy.precision ==
                                          "int8" else 5)


@pytest.mark.parametrize("billing", ["governor", "ledger"])
def test_host_replica_prices_each_lane_at_its_bucket_precision(billing):
    """The batcher's host replica prices every lane with
    ``EnergyModel.lane_pj`` at the precision of the bucket that served it,
    under the governor billing the lane's tenant; lanes no governor bills
    are NaN."""
    from repro.serve.governor import EnergyGovernor, TenantLedger
    models = {"a": EnergyModel(2, 8, 10, 16), "b": EnergyModel(4, 6, 10, 16)}

    def gov(model):
        return EnergyGovernor([FogPolicy(threshold=0.5)], None, model=model)

    if billing == "governor":
        governor, tenants = gov(models["a"]), [None] * 3
    else:
        governor = TenantLedger()
        governor.add("a", gov(models["a"]))
        governor.add("b", gov(models["b"]))
        tenants = ["a", "b", "c"]
    b = ContinuousBatcher(6, _precision_hops_decode, lambda s, p: len(p),
                          eos_id=-1, governor=governor)
    precs = ["int8", "fp32", None]
    for rid in range(6):
        # each tenant at two of the three precisions
        tenant, prec = tenants[rid % 3], precs[(rid // 3 + rid) % 3]
        b.submit(Request(rid=rid, prompt=np.asarray([rid]), max_new_tokens=1,
                         policy=FogPolicy(threshold=0.5, precision=prec),
                         model=tenant, version=None if tenant is None else 1))
    b.step()
    priced = 0
    for p in b.last_dispatches:
        hops = np.asarray(p.hops)[p.local]
        energy = np.asarray(p.energy)[p.local]
        assert energy.dtype == np.float32
        tenant = None if p.bucket is None else p.bucket[0]
        model = models.get(tenant or "a")
        if billing == "ledger" and tenant == "c":
            assert np.isnan(energy).all()
            continue
        want = dataclasses.replace(model, precision=p.precision or "fp32")
        np.testing.assert_allclose(energy, want.lane_pj(hops), rtol=1e-6)
        priced += len(p.lanes)
    assert priced == (6 if billing == "governor" else 4)
    assert b.stats.n_priced == priced


def test_host_replica_calls_of_one_step_see_the_same_inputs():
    """Two buckets a step: both calls read the same tokens and lengths, and
    each lane's next input is the token its OWN bucket's call produced."""
    seen = []

    def decode(tokens, lengths, policy):
        seen.append((policy.precision, np.asarray(tokens).tolist(),
                     np.asarray(lengths).tolist()))
        step = 1 if policy.precision == "int8" else 2
        logits = np.zeros((4, 64), np.float32)
        logits[np.arange(4), np.asarray(tokens) + step] = 1.0
        return jnp.asarray(logits), jnp.ones((4,), jnp.int32)

    b = ContinuousBatcher(4, decode, lambda s, p: len(p), eos_id=-1)
    for rid, prec in enumerate(["int8", "fp32", "int8", "fp32"]):
        b.submit(Request(rid=rid, prompt=np.asarray([7, 10 * (rid + 1)]),
                         max_new_tokens=3,
                         policy=FogPolicy(precision=prec)))
    done = {r.rid: r.generated for r in b.run()}
    assert done == {0: [11, 12, 13], 1: [22, 24, 26], 2: [31, 32, 33],
                    3: [42, 44, 46]}
    assert [s[0] for s in seen] == ["int8", "fp32"] * 3
    for step in range(3):
        a, c = seen[2 * step], seen[2 * step + 1]
        assert a[1:] == c[1:]
        assert a[2] == [2 + step] * 4
    assert seen[2][1] == [11, 22, 31, 42]


def test_host_replica_dispatch_does_not_wait_for_the_decode():
    """A dispatch over host replicas returns while their decodes still
    run: the argmax and the pricing are queued behind each decode on its
    device, so no replica waits for another's output."""
    from repro.serve.governor import EnergyGovernor, lane_pricer

    @jax.jit
    def slow_decode(tokens):
        # ~1 s of device work on a CPU whose outputs depend on all of it
        m = jnp.full((1024, 1024), 1e-3, jnp.float32)
        a = jax.lax.fori_loop(0, 256, lambda i, a: jnp.tanh(a @ m), m)
        return jax.nn.one_hot(tokens + 1, 16) + 0 * a[0, 0], tokens % 3 + 1

    def decode(tokens, lengths, policy):
        return slow_decode(tokens)

    gov = EnergyGovernor([FogPolicy()], None, model=EnergyModel(2, 8, 10, 16))
    disp = DeviceDispatcher(
        host_replica_factory(decode, lambda lane, prompt: len(prompt),
                             lane_pricer(gov)), [jax.devices()[0]] * 2)
    disp.bind(8)
    lanes = np.arange(8)
    disp.admit_lanes(lanes, [np.asarray([i]) for i in lanes],
                     np.full(8, 0.5, np.float32), np.full(8, NO_BUDGET))
    jax.block_until_ready(slow_decode(jnp.arange(4)))      # compile
    pend = disp.dispatch_packed(lanes, 0.5, NO_BUDGET)
    assert [p.device for p in pend] == [0, 1]
    assert not any(o.is_ready() for p in pend
                   for o in (p.nxt, p.hops, p.energy))
    nxt, hops, energy, _ = disp.harvest_packed(8)
    np.testing.assert_array_equal(nxt, np.arange(1, 9) % 16)
    np.testing.assert_array_equal(hops, np.arange(8) % 3 + 1)
    np.testing.assert_allclose(energy, gov.price(hops, None), rtol=1e-6)

"""ModelRegistry + PackCache (repro/registry/): versioned artifacts,
atomic publish/rollback, deterministic canary routing, the VMEM-budgeted
resident pack set, and the serving integration (version pinning across a
hot-swap, per-tenant governor independence behind one dispatcher)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EnergyModel, FogPolicy, split
from repro.forest import ForestPack
from repro.registry import ModelRegistry, PackCache
from repro.serve.dispatch import (DeviceDispatcher, ForestReplicaServer,
                                  host_replica_factory)
from repro.serve.governor import EnergyGovernor, TenantLedger, lane_pricer
from repro.serve.scheduler import ContinuousBatcher, Request


@pytest.fixture(scope="module")
def gc(trained):
    _, rf = trained
    return split(rf, 2)


@pytest.fixture(scope="module")
def pack(gc):
    return ForestPack.from_groves(gc)


# ---------------------------------------------------------------------------
# ModelRegistry: publish / rollback / canary lifecycle
# ---------------------------------------------------------------------------

def test_publish_is_monotonic_and_hot_swaps(tmp_path, pack):
    reg = ModelRegistry(tmp_path / "reg")
    assert reg.publish("t", pack) == 1
    assert reg.publish("t", pack) == 2          # hot-swap: live flips
    assert reg.tenants() == ["t"]
    assert reg.versions("t") == [1, 2]
    assert reg.live_version("t") == 2
    assert reg.canary("t") is None
    for v in (1, 2):                            # artifacts kept for rollback
        assert reg.artifact_path("t", v).is_file()
    assert (tmp_path / "reg" / "t" / "MANIFEST.json").is_file()


def test_fresh_instance_reloads_manifests(tmp_path, pack):
    root = tmp_path / "reg"
    reg = ModelRegistry(root)
    reg.publish("t", pack)
    reg.publish("t", pack)
    reg2 = ModelRegistry(root)                  # a new serving process
    assert reg2.live_version("t") == 2
    assert reg2.versions("t") == [1, 2]
    loaded, _ = reg2.load("t")
    assert loaded.precision == pack.precision
    np.testing.assert_array_equal(np.asarray(loaded.threshold),
                                  np.asarray(pack.threshold))


def test_tenant_name_and_canary_validation(tmp_path, pack):
    reg = ModelRegistry(tmp_path / "reg")
    for bad in ("", "a/b", ".hidden", "-x"):
        with pytest.raises(ValueError, match="invalid tenant"):
            reg.publish(bad, pack)
    with pytest.raises(ValueError, match="full publish"):
        reg.publish("t", pack, canary=0.1)      # no live to canary against
    reg.publish("t", pack)
    for frac in (0.0, 1.0, -0.2, 2.0):
        with pytest.raises(ValueError, match="fraction"):
            reg.publish("t", pack, canary=frac)
    with pytest.raises(ValueError, match="unknown tenant"):
        reg.route("ghost", 0)


def test_rollback_default_explicit_and_errors(tmp_path, pack):
    reg = ModelRegistry(tmp_path / "reg")
    for _ in range(3):
        reg.publish("t", pack)
    assert reg.rollback("t") == 2               # default: previous version
    assert reg.rollback("t", 1) == 1            # explicit target
    with pytest.raises(ValueError, match="nothing older"):
        reg.rollback("t")
    with pytest.raises(ValueError, match="no version"):
        reg.rollback("t", 99)
    # a rollback aborts any active canary: it is a judgment that the
    # newest code path misbehaves
    reg.publish("t", pack, canary=0.5)
    assert reg.canary("t") is not None
    reg.rollback("t", 3)
    assert reg.canary("t") is None
    assert reg.live_version("t") == 3


def test_canary_routing_deterministic_then_promote(tmp_path, pack):
    reg = ModelRegistry(tmp_path / "reg")
    reg.publish("t", pack)
    reg.publish("t", pack, canary=0.3)
    assert reg.live_version("t") == 1           # old live keeps serving
    assert reg.canary("t") == (2, 0.3)
    routes = [reg.route("t", rid) for rid in range(4000)]
    assert set(routes) == {1, 2}
    # pure function of (tenant, rid, manifest): retries never flap
    assert routes == [reg.route("t", rid) for rid in range(4000)]
    frac = np.mean(np.asarray(routes) == 2)
    assert frac == pytest.approx(0.3, abs=0.05)
    assert reg.promote("t") == 2
    assert reg.canary("t") is None
    assert {reg.route("t", rid) for rid in range(100)} == {2}
    with pytest.raises(ValueError, match="no active canary"):
        reg.promote("t")


def test_abort_canary_keeps_artifact(tmp_path, pack):
    reg = ModelRegistry(tmp_path / "reg")
    reg.publish("t", pack)
    reg.publish("t", pack, canary=0.2)
    reg.abort_canary("t")
    assert reg.live_version("t") == 1 and reg.canary("t") is None
    loaded, _ = reg.load("t", 2)                # artifact stays on disk
    assert loaded.table_bytes == pack.table_bytes
    assert reg.publish("t", pack) == 3          # numbering stays monotonic


def test_load_missing_artifact_is_loud(tmp_path, pack):
    reg = ModelRegistry(tmp_path / "reg")
    reg.publish("t", pack)
    reg.artifact_path("t", 1).unlink()
    with pytest.raises(ValueError, match="missing"):
        reg.load("t")


def test_judge_canary_reads_per_version_stats(tmp_path, pack):
    reg = ModelRegistry(tmp_path / "reg")
    reg.publish("t", pack)
    with pytest.raises(ValueError, match="no active canary"):
        reg.judge_canary("t")
    reg.publish("t", pack, canary=0.5)
    reg.stats_for("t", 1).update(np.full(4, 6), energy_pj=np.full(4, 2000.0))
    reg.stats_for("t", 2).update(np.full(4, 2), energy_pj=np.full(4, 500.0))
    j = reg.judge_canary("t")
    assert j["live_version"] == 1 and j["canary_version"] == 2
    assert j["canary_fraction"] == 0.5
    assert j["live"]["n_events"] == 4 and j["canary"]["n_events"] == 4
    assert j["live"]["mean_nj"] == pytest.approx(2.0)
    assert j["canary"]["mean_nj"] == pytest.approx(0.5)
    assert j["delta_nj"] == pytest.approx(-1.5)     # canary is cheaper


# ---------------------------------------------------------------------------
# PackCache: budget, weights, stale-first eviction
# ---------------------------------------------------------------------------

def test_cache_accounting_never_exceeds_budget(tmp_path, pack):
    reg = ModelRegistry(tmp_path / "reg")
    reg.publish("t", pack)
    reg.publish("t", pack)
    cache = PackCache(reg, budget_bytes=pack.table_bytes)  # exactly one fp32
    p1 = cache.get("t", 1)
    assert cache.stats.misses == 1
    assert cache.get("t", 1) is p1 and cache.stats.hits == 1
    cache.get("t", 2)                           # overflow: v1 evicted
    assert cache.stats.evictions == 1
    assert cache.keys() == [("t", 2, "fp32")]
    assert cache.bytes_used <= cache.budget_bytes
    assert cache.peak_bytes <= cache.budget_bytes
    # lazy reload after eviction: a miss, not an error
    assert cache.get("t", 1).table_bytes == pack.table_bytes
    assert cache.stats.misses == 3
    assert cache.stats.hit_rate == pytest.approx(1 / 4)


def test_cache_evicts_stale_version_before_hot_weight(tmp_path, pack):
    """A hot-swap's whole point is releasing the old version's tables:
    the demoted version must be the first eviction candidate even when its
    historical traffic weight dwarfs the live version's."""
    reg = ModelRegistry(tmp_path / "reg")
    reg.publish("t", pack)
    cache = PackCache(reg, budget_bytes=2 * pack.table_bytes)
    for _ in range(10):
        cache.get("t", 1)
    reg.publish("t", pack)                      # v1 is now stale
    cache.get("t", 2)
    for _ in range(5):
        cache.get("t", 1)                       # stale but historically hot
    assert cache.weight_of("t", 1, "fp32") > cache.weight_of("t", 2, "fp32")
    got = cache.get("t", 2, "int8")             # overflow forces eviction
    assert got.precision == "int8"              # astype on the way in
    assert ("t", 1, "fp32") not in cache.keys()
    assert ("t", 2, "fp32") in cache.keys()     # live survives, stale went
    assert cache.stats.evictions == 1
    assert cache.bytes_used <= cache.budget_bytes


def test_cache_seeds_new_entries_at_mean_weight(tmp_path, pack):
    """A fresh entry must compete fairly: seeded at weight 1.0 it would be
    the guaranteed eviction minimum against incumbents' accumulated hit
    counts, thrashing every newly-published version in and out forever."""
    reg = ModelRegistry(tmp_path / "reg")
    reg.publish("t", pack)
    reg.publish("t", pack)
    cache = PackCache(reg, budget_bytes=3 * pack.table_bytes)
    for _ in range(9):
        cache.get("t", 1)                       # weight 9 (1 miss + 8 hits)
    cache.get("t", 2)
    w1, w2 = cache.weight_of("t", 1, "fp32"), cache.weight_of("t", 2, "fp32")
    assert w2 == pytest.approx(w1)              # mean of {v1} = v1's weight
    assert w2 > 5.0                             # not the old 1.0 seeding


def test_cache_oversized_pack_and_ctor_validation(tmp_path, pack):
    reg = ModelRegistry(tmp_path / "reg")
    reg.publish("t", pack)
    cache = PackCache(reg, budget_bytes=pack.table_bytes - 1)
    with pytest.raises(ValueError, match="cache budget"):
        cache.get("t", 1)
    with pytest.raises(ValueError, match="budget_bytes"):
        PackCache(reg, budget_bytes=0)
    for decay in (0.0, 1.5):
        with pytest.raises(ValueError, match="decay"):
            PackCache(reg, budget_bytes=1024, decay=decay)


def test_cache_device_pack_committed_once_dropped_at_eviction(tmp_path, pack):
    reg = ModelRegistry(tmp_path / "reg")
    reg.publish("t", pack)
    cache = PackCache(reg, budget_bytes=2 * pack.table_bytes)
    dev = jax.devices()[0]
    c1 = cache.device_pack("t", 1, "fp32", 0, dev)
    assert cache.device_pack("t", 1, "fp32", 0, dev) is c1   # cached copy
    assert next(iter(c1.threshold.devices())) == dev
    assert cache.evict("t", 1, "fp32")
    assert not cache.evict("t", 1, "fp32")      # already gone
    c2 = cache.device_pack("t", 1, "fp32", 0, dev)           # fresh placement
    assert c2 is not c1


# ---------------------------------------------------------------------------
# serving integration: buckets, version pinning, ledger independence
# ---------------------------------------------------------------------------

def _bucket_decode(n_slots, vocab=16):
    """Bucket-aware mock: records each dispatch's (model, version) bucket;
    hops track each lane's threshold like the policy mocks do."""
    calls = []

    def decode_fn(tokens, lengths, policy, bucket=None):
        calls.append(bucket)
        thr = np.asarray(policy.lane_thresholds(n_slots))
        nxt = (np.asarray(tokens) + 1) % vocab
        logits = np.zeros((n_slots, vocab), np.float32)
        logits[np.arange(n_slots), nxt] = 1.0
        hops = np.maximum(1, np.round(thr * 10)).astype(np.int64)
        return jnp.asarray(logits), jnp.asarray(hops)

    return decode_fn, calls


def test_request_model_validated_at_submit():
    decode_fn, _ = _bucket_decode(2)
    b = ContinuousBatcher(2, decode_fn, lambda s, p: len(p), eos_id=-1)
    with pytest.raises(ValueError, match="registry"):
        b.submit(Request(rid=0, prompt=np.asarray([0]), model="t"))
    # a pre-set version bypasses routing (no registry needed)
    assert b.submit(Request(rid=1, prompt=np.asarray([0]), model="t",
                            version=1))

    def plain(tokens, lengths, policy):
        return None, None

    b2 = ContinuousBatcher(2, plain, lambda s, p: len(p), eos_id=-1)
    with pytest.raises(ValueError, match="bucket-aware"):
        b2.submit(Request(rid=0, prompt=np.asarray([0]), model="t",
                          version=1))


def test_hot_swap_pins_inflight_versions(tmp_path, pack):
    """Zero-downtime hot-swap: a publish mid-decode must not migrate
    in-flight requests (version pinned at slot assignment) while new
    arrivals route to the new live version — and per-version ServeStats
    split the telemetry accordingly."""
    reg = ModelRegistry(tmp_path / "reg")
    reg.publish("t", pack)
    n = 2
    decode_fn, calls = _bucket_decode(n)
    b = ContinuousBatcher(n, decode_fn, lambda s, p: len(p), eos_id=-1,
                          registry=reg)
    for rid in range(2):
        b.submit(Request(rid=rid, prompt=np.asarray([0]), max_new_tokens=3,
                         model="t"))
    b.step()                                    # slots assigned: pinned to v1
    assert all(s.request.version == 1 for s in b.slots)
    reg.publish("t", pack)                      # hot-swap mid-flight
    for rid in range(2, 4):
        b.submit(Request(rid=rid, prompt=np.asarray([0]), max_new_tokens=3,
                         model="t"))
    done = b.run()
    versions = {r.rid: r.version for r in done}
    assert versions[0] == versions[1] == 1      # in-flight stayed put
    assert versions[2] == versions[3] == 2      # new arrivals on new live
    assert {("t", 1), ("t", 2)} <= set(calls)
    assert reg.stats_for("t", 1).n_events == 6
    assert reg.stats_for("t", 2).n_events == 6


def test_tenant_governors_independent_behind_one_dispatcher():
    """Two ledgered tenants share ONE data-parallel plane: the expensive
    tenant's breach walks ITS OWN ladder down and must neither move the
    frugal tenant's rung nor pollute its rolling estimate; both governors
    still get device-labeled telemetry from the shared dispatcher."""
    model = EnergyModel(2, 8, 10, 16)
    ladder = [FogPolicy(threshold=0.8), FogPolicy(threshold=0.1)]
    def mk(budget):
        return EnergyGovernor(list(ladder), budget, model=model,
                              window=4, patience=2, cooldown=10_000)

    eight_hop_nj = float(np.asarray(model.lane_pj(np.asarray([8]))[0])) * 1e-3
    gov_a = mk(eight_hop_nj * 0.5)              # rung 0 unaffordable
    gov_b = mk(eight_hop_nj * 4.0)              # comfortable at rung 0
    ledger = TenantLedger()
    ledger.add("a", gov_a)
    ledger.add("b", gov_b)

    def decode(tokens, lengths, policy, bucket=None):
        span = np.asarray(tokens).shape[0]
        thr = np.asarray(policy.lane_thresholds(span))
        nxt = (np.asarray(tokens) + 1) % 16
        logits = np.zeros((span, 16), np.float32)
        logits[np.arange(span), nxt] = 1.0
        hops = np.maximum(1, np.round(thr * 10)).astype(np.int64)
        return jnp.asarray(logits), jnp.asarray(hops)

    disp = DeviceDispatcher(
        host_replica_factory(decode, lambda s, p: len(p),
                             price=lane_pricer(ledger)),
        [jax.devices()[0]] * 2)
    b = ContinuousBatcher(4, None, lambda s, p: len(p), eos_id=-1,
                          governor=ledger, dispatcher=disp)
    for rid in range(8):
        b.submit(Request(rid=rid, prompt=np.asarray([0]), max_new_tokens=4,
                         model="a" if rid % 2 == 0 else "b", version=1))
    done = b.run()
    assert len(done) == 8
    # tenant a breached and settled one rung down; tenant b never moved
    assert [t[:2] for t in gov_a.transitions] == [(0, 1)]
    assert gov_a.rung == 1
    assert gov_b.transitions == [] and gov_b.rung == 0
    # b's estimate reflects ONLY its own 8-hop traffic (no cross-tenant
    # averaging with a's post-step-down 1-hop lanes)
    assert gov_b.rolling_nj == pytest.approx(eight_hop_nj)
    # the shared dispatcher labeled both tenants' telemetry per device
    for gov in (gov_a, gov_b):
        summary = gov.device_summary()
        assert {0, 1} <= set(summary)
        assert summary[None]["spread_nj"] == pytest.approx(0.0, abs=1e-12)


def test_registry_mode_server_serves_through_cache(trained, tmp_path):
    """The real thing, small: a registry-mode ForestReplicaServer (no
    built-in model) classifies a tenant's traffic through the VMEM-budgeted
    cache at forest quality, one artifact load for the whole run."""
    ds, rf = trained
    gc2 = split(rf, 2)
    p = ForestPack.from_groves(gc2)
    reg = ModelRegistry(tmp_path / "reg")
    reg.publish("t", p, extra={"n_features_in": ds.x_test.shape[1]})
    cache = PackCache(reg, budget_bytes=2 * p.table_bytes)
    server = ForestReplicaServer(None, ds.x_test.shape[1], backend="fused",
                                 registry=reg, cache=cache)
    disp = DeviceDispatcher(server.packed_factory, [jax.devices()[0]])
    b = ContinuousBatcher(8, None, server.prefill, eos_id=-1,
                          default_policy=FogPolicy(threshold=0.7),
                          dispatcher=disp, registry=reg)
    n = 24
    for rid in range(n):
        b.submit(Request(rid=rid, prompt=ds.x_test[rid], max_new_tokens=1,
                         model="t"))
    done = b.run()
    assert len(done) == n
    assert all(r.version == 1 for r in done)
    preds = np.array([r.generated[0]
                      for r in sorted(done, key=lambda r: r.rid)])
    acc = float((preds == ds.y_test[:n]).mean())
    assert acc > 0.7
    assert all(r.hops[0] >= 1 for r in done)
    assert cache.stats.misses == 1              # one load, then resident
    assert cache.stats.hits >= 2
    assert reg.stats_for("t", 1).n_events == n

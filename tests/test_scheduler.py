"""Continuous-batching scheduler (serve/scheduler.py).

The mocks are host ``decode_fn(tokens, lengths, policy)`` callables; the
batcher serves them through a one-replica dispatcher over
:class:`~repro.serve.dispatch.HostReplica`, the same step, dispatch /
harvest cycle and telemetry the forest's packed replicas run."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import NO_BUDGET, FogPolicy
from repro.serve.scheduler import ContinuousBatcher, Request


def _mock_decode(n_slots, vocab=16, eos=1):
    """Deterministic mock: token t -> (t+1) % vocab; hops = 1 + slot%3."""
    def decode_fn(tokens, lengths, policy):
        nxt = (np.asarray(tokens) + 1) % vocab
        logits = np.zeros((n_slots, vocab), np.float32)
        logits[np.arange(n_slots), nxt] = 1.0
        hops = 1 + np.arange(n_slots) % 3
        return jnp.asarray(logits), jnp.asarray(hops)
    return decode_fn


def test_all_requests_complete():
    n = 4
    batcher = ContinuousBatcher(n, _mock_decode(n),
                                lambda slot, prompt: len(prompt), eos_id=-1)
    for rid in range(10):
        batcher.submit(Request(rid=rid, prompt=np.asarray([2, 3]),
                               max_new_tokens=5))
    done = batcher.run()
    assert len(done) == 10
    assert all(len(r.generated) == 5 for r in done)
    # deterministic generation: 3 -> 4 -> 5 ...
    assert done[0].generated[:3] == [4, 5, 6]


def test_eos_terminates_early():
    n = 2
    batcher = ContinuousBatcher(n, _mock_decode(n, eos=1),
                                lambda slot, prompt: len(prompt), eos_id=4)
    batcher.submit(Request(rid=0, prompt=np.asarray([3]), max_new_tokens=50))
    done = batcher.run()
    assert done[0].generated == [4]          # 3 -> 4 == eos


def test_slots_refilled_continuously():
    """More requests than slots: every request still finishes, and the
    batcher never runs more than n_slots concurrently."""
    n = 2
    batcher = ContinuousBatcher(n, _mock_decode(n),
                                lambda slot, prompt: len(prompt), eos_id=-1)
    for rid in range(7):
        batcher.submit(Request(rid=rid, prompt=np.asarray([0]),
                               max_new_tokens=3))
    steps = 0
    while batcher.queue or batcher.active:
        assert batcher.active <= n
        batcher.step()
        steps += 1
        assert steps < 100
    assert len(batcher.completed) == 7


def test_hops_metering_accumulates():
    n = 3
    batcher = ContinuousBatcher(n, _mock_decode(n),
                                lambda slot, prompt: len(prompt), eos_id=-1)
    batcher.submit(Request(rid=0, prompt=np.asarray([0]), max_new_tokens=4))
    done = batcher.run()
    assert len(done[0].hops) == 4
    assert all(h >= 1 for h in done[0].hops)


def test_serve_stats_accumulate_and_reset():
    n = 2
    batcher = ContinuousBatcher(n, _mock_decode(n),
                                lambda slot, prompt: len(prompt), eos_id=-1)
    batcher.submit(Request(rid=0, prompt=np.asarray([0]), max_new_tokens=3))
    batcher.run()
    assert batcher.stats.n_events == 3
    assert not batcher.stats.has_energy      # no governor: hops only
    batcher.stats.reset()
    assert batcher.stats.n_events == 0 and batcher.stats.total_hops == 0
    assert batcher.stats.mean_hops == 0.0


def _mock_policy_decode(n_slots, vocab=16):
    """Policy-aware mock: hops = each lane's threshold * 10 (so tests can
    read back exactly which per-lane vector the batcher assembled)."""
    seen = []

    def decode_fn(tokens, lengths, policy):
        assert isinstance(policy, FogPolicy)
        seen.append((np.asarray(policy.lane_thresholds(n_slots)),
                     np.asarray(policy.lane_budgets(n_slots))))
        nxt = (np.asarray(tokens) + 1) % vocab
        logits = np.zeros((n_slots, vocab), np.float32)
        logits[np.arange(n_slots), nxt] = 1.0
        hops = np.round(seen[-1][0] * 10).astype(np.int32)
        return jnp.asarray(logits), jnp.asarray(hops)

    return decode_fn, seen


def test_mixed_qos_per_request_policies():
    """Two QoS tiers in ONE continuous batch: the batcher must assemble the
    slots' scalar policies into per-lane vectors every step, and each
    request's hop accounting must reflect ITS OWN threshold."""
    n = 2
    decode_fn, seen = _mock_policy_decode(n)
    batcher = ContinuousBatcher(
        n, decode_fn, lambda slot, prompt: len(prompt), eos_id=-1,
        default_policy=FogPolicy(threshold=0.3))
    batcher.submit(Request(rid=0, prompt=np.asarray([0]), max_new_tokens=3,
                           policy=FogPolicy(threshold=0.1)))
    batcher.submit(Request(rid=1, prompt=np.asarray([0]), max_new_tokens=3,
                           policy=FogPolicy(threshold=0.9, hop_budget=2)))
    done = batcher.run()
    by_rid = {r.rid: r for r in done}
    assert by_rid[0].hops == [1, 1, 1]            # thresh 0.1 -> mock hops 1
    assert by_rid[1].hops == [9, 9, 9]            # thresh 0.9 -> mock hops 9
    thr0, bud0 = seen[0]
    np.testing.assert_allclose(thr0, [0.1, 0.9])
    np.testing.assert_array_equal(bud0, [NO_BUDGET, 2])


def test_empty_slots_get_default_policy():
    n = 3
    decode_fn, seen = _mock_policy_decode(n)
    batcher = ContinuousBatcher(
        n, decode_fn, lambda slot, prompt: len(prompt), eos_id=-1,
        default_policy=FogPolicy(threshold=0.5))
    batcher.submit(Request(rid=0, prompt=np.asarray([0]), max_new_tokens=1))
    batcher.run()
    thr, _ = seen[0]
    np.testing.assert_allclose(thr, [0.5, 0.5, 0.5])  # req slot + 2 empty


def test_per_lane_request_policy_rejected():
    n = 2
    decode_fn, _ = _mock_policy_decode(n)
    batcher = ContinuousBatcher(n, decode_fn,
                                lambda slot, prompt: len(prompt))
    with pytest.raises(ValueError):
        batcher.submit(Request(
            rid=0, prompt=np.asarray([0]),
            policy=FogPolicy(threshold=jnp.asarray([0.1, 0.2]))))


def test_static_knobs_on_request_policy_rejected():
    """max_hops/backend/... select the compiled program — they cannot vary
    per request and must be rejected loudly, not silently dropped."""
    n = 2
    decode_fn, _ = _mock_policy_decode(n)
    batcher = ContinuousBatcher(n, decode_fn,
                                lambda slot, prompt: len(prompt))
    with pytest.raises(ValueError, match="static knobs"):
        batcher.submit(Request(rid=0, prompt=np.asarray([0]),
                               policy=FogPolicy(threshold=0.1, max_hops=2)))
    with pytest.raises(ValueError, match="static knobs"):
        batcher.submit(Request(rid=1, prompt=np.asarray([0]),
                               policy=FogPolicy(backend="pallas")))



@pytest.mark.parametrize("bad", [
    FogPolicy(threshold=jnp.asarray([0.1, 0.2])),
    FogPolicy(threshold=0.1, max_hops=2),
    FogPolicy(backend="pallas")], ids=["per_lane", "max_hops", "backend"])
def test_invalid_policy_raises_on_every_submit(bad):
    """submit() validates each distinct policy object once and remembers
    only valid ones: an invalid policy raises on every submit, before and
    after a valid policy has been validated and cached."""
    n = 2
    decode_fn, _ = _mock_policy_decode(n)
    batcher = ContinuousBatcher(n, decode_fn,
                                lambda slot, prompt: len(prompt))
    good = FogPolicy(threshold=0.5, precision="int8")
    batcher.trace = True
    try:
        for rid in range(3):
            with pytest.raises(ValueError):
                batcher.submit(Request(rid=rid, prompt=np.asarray([0]),
                                       policy=bad))
            assert batcher.submit(Request(rid=10 + rid,
                                          prompt=np.asarray([0]),
                                          policy=good))
    finally:
        batcher.trace = False
    assert [r.rid for r in batcher.queue] == [10, 11, 12]
    assert batcher.stats.n_offered == 3
    # three refusals of the bad policy, one validation of the good one
    assert batcher.counts["policy_checks"] == 4


def test_energy_budget_requests_resolve_past_the_policy_cache():
    """A request with an energy budget gets a fresh policy from the
    governor on every submit (never a cached one), and each resolves to
    the rung its budget buys."""
    n = 2
    gov = _governor(budget_nj=2.0)
    batcher = ContinuousBatcher(n, _threshold_driven_decode(n),
                                lambda slot, prompt: len(prompt), eos_id=-1,
                                governor=gov)
    batcher.trace = True
    try:
        reqs = [Request(rid=rid, prompt=np.asarray([0]), max_new_tokens=1,
                        energy_budget_nj=0.4) for rid in range(3)]
        for r in reqs:
            batcher.submit(r)
    finally:
        batcher.trace = False
    assert len({id(r.policy) for r in reqs}) == 3
    assert all(int(np.asarray(r.policy.hop_budget)) == 1 for r in reqs)
    assert batcher.counts["policy_checks"] == 3
    assert all(h == [1] for h in (r.hops for r in batcher.run()))


def _mock_precision_decode(n_slots, vocab=16):
    """Precision-aware mock: records each dispatch's precision and encodes
    it into hops (fp32 -> 32, int8 -> 8, default/None -> 1), so tests can
    see exactly which program served which slot."""
    calls = []
    code = {None: 1, "fp32": 32, "bf16": 16, "int8": 8}

    def decode_fn(tokens, lengths, policy):
        calls.append(policy.precision)
        nxt = (np.asarray(tokens) + 1) % vocab
        logits = np.zeros((n_slots, vocab), np.float32)
        logits[np.arange(n_slots), nxt] = 1.0
        hops = np.full((n_slots,), code[policy.precision], np.int32)
        return jnp.asarray(logits), jnp.asarray(hops)

    return decode_fn, calls


def test_per_request_precision_not_rejected():
    """precision is the one static knob a request may set — the batcher
    handles it by bucketed dispatch instead of rejecting it."""
    n = 2
    decode_fn, _ = _mock_precision_decode(n)
    batcher = ContinuousBatcher(n, decode_fn,
                                lambda slot, prompt: len(prompt))
    batcher.submit(Request(rid=0, prompt=np.asarray([0]),
                           policy=FogPolicy(precision="int8")))   # no raise


def test_mixed_precision_buckets_dispatch_per_group():
    """Two precisions in one continuous batch: one dispatch per distinct
    precision per step, and every request's outputs come from ITS OWN
    precision's program."""
    n = 3
    decode_fn, calls = _mock_precision_decode(n)
    batcher = ContinuousBatcher(
        n, decode_fn, lambda slot, prompt: len(prompt), eos_id=-1,
        default_policy=FogPolicy(threshold=0.3))
    batcher.submit(Request(rid=0, prompt=np.asarray([0]), max_new_tokens=2,
                           policy=FogPolicy(precision="int8")))
    batcher.submit(Request(rid=1, prompt=np.asarray([0]), max_new_tokens=2,
                           policy=FogPolicy(precision="fp32")))
    batcher.submit(Request(rid=2, prompt=np.asarray([0]), max_new_tokens=2))
    done = batcher.run()
    by_rid = {r.rid: r for r in done}
    assert by_rid[0].hops == [8, 8]         # served by the int8 program
    assert by_rid[1].hops == [32, 32]       # served by the fp32 program
    assert by_rid[2].hops == [1, 1]         # default program
    # 3 groups active for 2 steps -> 6 dispatches, all precisions present
    assert len(calls) == 6
    assert set(calls) == {None, "fp32", "int8"}


def test_homogeneous_precision_costs_one_dispatch():
    """All requests on one precision (or none): exactly one decode dispatch
    per step — bucketing must not tax the common case."""
    n = 2
    decode_fn, calls = _mock_precision_decode(n)
    batcher = ContinuousBatcher(n, decode_fn,
                                lambda slot, prompt: len(prompt), eos_id=-1)
    batcher.submit(Request(rid=0, prompt=np.asarray([0]), max_new_tokens=3,
                           policy=FogPolicy(precision="int8")))
    batcher.run()
    assert calls == ["int8", "int8", "int8"]


def test_per_lane_default_policy_rejected_at_construction():
    n = 2
    decode_fn, _ = _mock_policy_decode(n)
    with pytest.raises(ValueError):
        ContinuousBatcher(n, decode_fn, lambda slot, prompt: len(prompt),
                          default_policy=FogPolicy(
                              threshold=jnp.asarray([0.1, 0.2])))


# ---------------------------------------------------------------------------
# energy governance (the EnergyGovernor control plane)
# ---------------------------------------------------------------------------

def _threshold_driven_decode(n_slots, vocab=16):
    """Governor-visible mock: hops track each lane's threshold (tighter
    threshold -> earlier exit), capped by the lane's hop budget — the same
    monotone response a real forest has."""
    def decode_fn(tokens, lengths, policy):
        thr = np.asarray(policy.lane_thresholds(n_slots))
        bud = np.asarray(policy.lane_budgets(n_slots))
        hops = np.minimum(np.maximum(1, np.round(thr * 10)).astype(np.int64),
                          bud)
        nxt = (np.asarray(tokens) + 1) % vocab
        logits = np.zeros((n_slots, vocab), np.float32)
        logits[np.arange(n_slots), nxt] = 1.0
        return jnp.asarray(logits), jnp.asarray(hops)
    return decode_fn


def _governor(budget_nj, base_thresh=0.5, **kw):
    from repro.core import EnergyModel
    from repro.serve.governor import EnergyGovernor, default_ladder
    model = EnergyModel(2, 8, 10, 16)
    ladder = default_ladder(FogPolicy(threshold=base_thresh), model,
                            budget_nj)
    kw.setdefault("window", 4)
    kw.setdefault("patience", 2)
    # long cooldown: a rung measured over budget stays blocked for the
    # whole test run (deterministic steady state)
    kw.setdefault("cooldown", 10_000)
    return EnergyGovernor(ladder, budget_nj, model=model, **kw)


def test_governor_steps_down_ladder_and_holds_budget():
    """The acceptance loop: under a tight budget the governor must walk
    down the ladder (threshold tightening, then the hop-budget rung) until
    the rolling estimate sits under the SLO, and fleet telemetry must show
    priced energy."""
    n = 2
    gov = _governor(budget_nj=0.5)
    batcher = ContinuousBatcher(n, _threshold_driven_decode(n),
                                lambda slot, prompt: len(prompt), eos_id=-1,
                                governor=gov)
    for rid in range(8):
        batcher.submit(Request(rid=rid, prompt=np.asarray([0]),
                               max_new_tokens=6))
    batcher.run()
    assert gov.transitions, "governor never stepped"
    assert gov.transitions[0][:2] == (0, 1), "first step must tighten"
    assert gov.rolling_nj <= gov.budget_nj          # steady state: under SLO
    assert batcher.stats.has_energy
    assert batcher.stats.n_events > 0


def test_governor_rejects_ungovernable_decode_paths():
    """A governor that can never act must fail loudly, not serve at full
    energy under the illusion of an SLO: a governor without an energy model
    is rejected at construction, hop-less telemetry on the first step."""
    from repro.serve.governor import EnergyGovernor
    with pytest.raises(ValueError, match="energy model"):
        ContinuousBatcher(2, _mock_decode(2), lambda slot, prompt: 1,
                          governor=EnergyGovernor([FogPolicy()], 1.0))

    def no_hops(tokens, lengths, policy):
        n = tokens.shape[0]
        logits = np.zeros((n, 16), np.float32)
        return jnp.asarray(logits), None

    b = ContinuousBatcher(2, no_hops, lambda slot, prompt: 1, eos_id=-1,
                          governor=_governor(budget_nj=1.0))
    b.submit(Request(rid=0, prompt=np.asarray([0]), max_new_tokens=2))
    with pytest.raises(ValueError, match="hop telemetry"):
        b.step()


def test_governor_restores_quality_when_headroom_returns():
    from repro.core import EnergyModel, FogPolicy as FP
    from repro.serve.governor import EnergyGovernor
    model = EnergyModel(2, 8, 10, 16)
    gov = EnergyGovernor([FP(threshold=0.5), FP(threshold=0.1)],
                         budget_nj=1.0, model=model, window=4, patience=2,
                         cooldown=8)
    # breach: expensive batches push it down a rung (and the breach is
    # remembered, so an immediate climb is blocked)
    gov.observe(hops=np.full(8, 8)); gov.step()
    assert gov.rung == 1
    # sustained headroom: once the breach evidence goes stale (cooldown),
    # two compliant observations climb back up
    for _ in range(2):
        gov.observe(hops=np.ones(8, np.int64)); gov.step()
    assert gov.rung == 0
    assert len(gov.transitions) == 2


def test_per_request_energy_budget_resolved_via_governor():
    """A Request carrying energy_budget_nj gets the calibrated rung fitting
    that budget, with a hard hop-budget clamp — submitted against a
    governor-less batcher it must fail loudly."""
    n = 2
    gov = _governor(budget_nj=2.0)
    decode_fn = _threshold_driven_decode(n)
    batcher = ContinuousBatcher(n, decode_fn,
                                lambda slot, prompt: len(prompt), eos_id=-1,
                                governor=gov)
    req = Request(rid=0, prompt=np.asarray([0]), max_new_tokens=2,
                  energy_budget_nj=0.4)
    batcher.submit(req)
    assert req.policy is not None
    # 0.4 nJ buys exactly one 271 pJ hop on the 2x8 topology model
    assert int(np.asarray(req.policy.hop_budget)) == 1
    done = batcher.run()
    assert all(h == 1 for h in done[0].hops)        # contract held

    plain = ContinuousBatcher(n, decode_fn, lambda slot, prompt: len(prompt))
    with pytest.raises(ValueError, match="governor"):
        plain.submit(Request(rid=1, prompt=np.asarray([0]),
                             energy_budget_nj=1.0))

    # a ladder built from a fleet default with STATIC knobs (backend,
    # max_hops) must not trip submit()'s static-knob rejection: the
    # resolved per-request contract carries only threshold/budget/precision
    from repro.core import EnergyModel
    from repro.serve.governor import EnergyGovernor, default_ladder
    model = EnergyModel(2, 8, 10, 16)
    base = FogPolicy(threshold=0.5, backend="reference", max_hops=8)
    gov2 = EnergyGovernor(default_ladder(base, model, 0.4), 0.4, model=model)
    b2 = ContinuousBatcher(n, decode_fn, lambda slot, prompt: len(prompt),
                           eos_id=-1, governor=gov2)
    req2 = Request(rid=5, prompt=np.asarray([0]), max_new_tokens=1,
                   energy_budget_nj=0.4)
    b2.submit(req2)                              # no raise
    assert req2.policy.backend is None and req2.policy.max_hops is None
    assert int(np.asarray(req2.policy.hop_budget)) == 1
    with pytest.raises(ValueError, match="not both"):
        batcher.submit(Request(rid=2, prompt=np.asarray([0]),
                               policy=FogPolicy(threshold=0.1),
                               energy_budget_nj=1.0))


# -- serving-layer bug sweep: admission, stats ------------------------------

def test_admission_reject_sheds_incoming():
    n = 1
    b = ContinuousBatcher(n, _mock_decode(n),
                          lambda slot, prompt: len(prompt), eos_id=-1,
                          max_queue=2, shed_policy="reject")
    admitted = [b.submit(Request(rid=rid, prompt=np.asarray([0]),
                                 max_new_tokens=1)) for rid in range(5)]
    assert admitted == [True, True, False, False, False]
    assert b.stats.n_offered == 5 and b.stats.n_shed == 3
    assert b.stats.shed_rate == pytest.approx(0.6)
    assert [r.rid for r in b.shed_requests] == [2, 3, 4]
    assert all(r.shed for r in b.shed_requests)
    done = b.run()
    assert sorted(r.rid for r in done) == [0, 1]


def test_admission_oldest_evicts_queue_head():
    n = 1
    b = ContinuousBatcher(n, _mock_decode(n),
                          lambda slot, prompt: len(prompt), eos_id=-1,
                          max_queue=2, shed_policy="oldest")
    admitted = [b.submit(Request(rid=rid, prompt=np.asarray([0]),
                                 max_new_tokens=1)) for rid in range(4)]
    assert admitted == [True, True, True, True]    # newcomers always admitted
    assert [r.rid for r in b.shed_requests] == [0, 1]
    done = b.run()
    assert sorted(r.rid for r in done) == [2, 3]


def test_admission_validation():
    n = 1
    with pytest.raises(ValueError, match="max_queue"):
        ContinuousBatcher(n, _mock_decode(n), lambda s, p: len(p),
                          max_queue=0)
    with pytest.raises(ValueError, match="shed_policy"):
        ContinuousBatcher(n, _mock_decode(n), lambda s, p: len(p),
                          shed_policy="drop-newest")


def test_shed_requests_stamp_t_submit_and_tier():
    """Shedding is part of the latency story: a rejected request must still
    carry its submit timestamp, and the shed must land in its own QoS
    tier's counters, not just the fleet total."""
    n = 1
    b = ContinuousBatcher(n, _mock_decode(n),
                          lambda slot, prompt: len(prompt), eos_id=-1,
                          max_queue=2, shed_policy="reject")
    b.submit(Request(rid=0, prompt=np.asarray([0]), max_new_tokens=1,
                     tier="std"))
    b.submit(Request(rid=1, prompt=np.asarray([0]), max_new_tokens=1,
                     tier="std"))
    shed = Request(rid=2, prompt=np.asarray([0]), max_new_tokens=1,
                   tier="bulk")
    assert not b.submit(shed)
    assert shed.shed and shed.t_submit is not None
    done = b.run()
    assert all(r.t_done is not None and r.t_done >= r.t_submit for r in done)
    ts = b.stats.tier_summary()
    assert ts["std"]["n_done"] == 2 and ts["std"]["n_shed"] == 0
    assert ts["bulk"]["n_shed"] == 1 and ts["bulk"]["n_done"] == 0


def test_tier_breakdown_prices_per_tier():
    """Per-tier energy means: the gold tier's expensive threshold must show
    up in ITS tier row, not be averaged away into the fleet mean."""
    n = 2
    gov = _governor(budget_nj=None)
    b = ContinuousBatcher(n, _threshold_driven_decode(n),
                          lambda slot, prompt: len(prompt), eos_id=-1,
                          governor=gov)
    b.submit(Request(rid=0, prompt=np.asarray([0]), max_new_tokens=3,
                     tier="gold", policy=FogPolicy(threshold=0.9)))
    b.submit(Request(rid=1, prompt=np.asarray([0]), max_new_tokens=3,
                     tier="bulk", policy=FogPolicy(threshold=0.1)))
    b.run()
    ts = b.stats.tier_summary()
    assert set(ts) == {"gold", "bulk"}
    for tier in ("gold", "bulk"):
        assert ts[tier]["n_done"] == 1 and ts[tier]["n_events"] == 3
    assert ts["gold"]["mean_energy_nj"] > ts["bulk"]["mean_energy_nj"] > 0
    # the fleet mean sits between the tier means
    fleet = b.stats.mean_energy_nj
    assert ts["bulk"]["mean_energy_nj"] < fleet < ts["gold"]["mean_energy_nj"]


def test_mean_energy_nj_divides_by_priced_events_only():
    """Mixing priced and unpriced updates must not deflate the mean: 4
    events at 2000 pJ plus 4 hops-only events is 2 nJ/event, not 1."""
    from repro.serve.scheduler import ServeStats
    stats = ServeStats()
    stats.update(np.full(4, 3), energy_pj=np.full(4, 2000.0))
    stats.update(np.full(4, 3))                    # unpriced telemetry
    assert stats.n_events == 8 and stats.n_priced == 4
    assert stats.mean_energy_nj == pytest.approx(2.0)
    stats.reset()
    assert stats.n_priced == 0 and stats.mean_energy_nj == 0.0


# -- the one step reproduces the retired per-request step ------------------
#
# Each scenario below was run once on the scheduler's former second step (a
# per-request refill, one decode call per bucket with full-batch per-lane
# vectors, host argmax, host pricing and inline per-request accounting) and
# its results pinned here.  The one step must reproduce them: the same
# tokens and hops per request, the same completion order, the same
# telemetry totals and governor transitions.  Energy is priced in float32
# on the replica now (as the forest's is), hence its tolerance.

_PIN_VOCAB, _PIN_EOS = 11, 7
_PIN_CODE = {None: 0, "fp32": 1, "int8": 2}
_PIN_POLICIES = [FogPolicy(threshold=0.3, precision="int8"), None,
                 FogPolicy(threshold=0.6, precision="fp32")]

_PINNED = {
    "governor": {
        "order": [0, 1, 5, 4, 2, 3, 8, 6, 9, 7, 12, 10, 13, 11],
        "generated": {0: [4, 6], 1: [5, 7], 5: [7], 4: [5, 9],
                      2: [9, 9, 10, 3], 3: [2, 0, 6, 3, 6], 8: [5, 8],
                      6: [0, 5, 10, 4], 9: [9, 10, 3], 7: [3, 3, 4, 8, 10],
                      12: [7], 10: [1, 8, 8, 9], 13: [10, 2, 1],
                      11: [3, 2, 0, 6, 3]},
        "hops": {0: [5, 5], 1: [5, 2], 5: [7], 4: [4, 3], 2: [7, 7, 7, 7],
                 3: [5, 5, 5, 5, 5], 8: [7, 7], 6: [5, 5, 5, 5],
                 9: [5, 5, 5], 7: [3, 3, 3, 3, 3], 12: [5],
                 10: [3, 3, 3, 3], 13: [3, 3, 3], 11: [7, 7, 7, 7, 7]},
        "stats": (209, 43, 43, 61201.99),
        "tiers": {"bulk": (7, 24, 1.3760595833333331),
                  "gold": (7, 19, 1.4829768421052634)},
        "transitions": {"fleet": [(0, 1), (1, 2), (2, 3)]},
    },
    "ledger": {
        "order": [0, 1, 4, 2, 5, 3, 8, 7, 9, 6, 12, 10, 13, 11],
        "generated": {0: [5, 10], 1: [6, 0, 7], 4: [4, 5], 2: [0, 6, 3, 6],
                      5: [8, 7], 3: [4, 8, 10, 6, 6], 8: [7], 7: [4, 7],
                      9: [0, 7], 6: [1, 9, 1, 0], 12: [8, 8],
                      10: [0, 4, 6, 2], 13: [0, 6, 3], 11: [4, 6, 2, 2, 3]},
        "hops": {0: [6, 6], 1: [6, 3, 5], 4: [4, 4], 2: [9, 9, 9, 9],
                 5: [8, 8], 3: [7, 7, 7, 7, 7], 8: [9], 7: [2, 2],
                 9: [7, 7], 6: [6, 6, 6, 6], 12: [6, 6], 10: [4, 4, 4, 4],
                 13: [2, 2, 2], 11: [8, 8, 8, 8, 8]},
        "stats": (246, 41, 28, 47944.26),
        "tiers": {"bulk": (7, 22, 1.3340686666666668),
                  "gold": (7, 19, 2.14871)},
        "transitions": {"a": [(0, 1), (1, 2), (2, 3)], "bb": []},
    },
}


def _pin_decode(tokens, lengths, policy, bucket=None):
    """Tokens depend on each lane's token, length, precision and tenant;
    hops on its threshold, hop budget, precision and tenant."""
    n = np.asarray(tokens).shape[0]
    code = _PIN_CODE[policy.precision] + (0 if bucket is None
                                          else len(bucket[0]))
    thr = np.asarray(policy.lane_thresholds(n))
    bud = np.asarray(policy.lane_budgets(n))
    hops = np.minimum(np.maximum(1, np.round(thr * 10)).astype(np.int64)
                      + code, bud)
    nxt = (np.asarray(tokens) * 3 + np.asarray(lengths) + code) % _PIN_VOCAB
    logits = np.zeros((n, _PIN_VOCAB), np.float32)
    logits[np.arange(n), nxt] = 1.0
    return jnp.asarray(logits), jnp.asarray(hops)


def _pin_governor(budget_nj):
    from repro.core import EnergyModel
    from repro.serve.governor import EnergyGovernor, default_ladder
    model = EnergyModel(2, 8, 10, 16)
    return EnergyGovernor(
        default_ladder(FogPolicy(threshold=0.5), model, budget_nj),
        budget_nj, model=model, window=4, patience=2, cooldown=10_000)


@pytest.mark.parametrize("kind", ["governor", "ledger"])
def test_one_step_reproduces_the_retired_step(kind):
    """Two precisions, EOS and max_new_tokens > 1, under a fleet governor
    that steps down three rungs, or a ledger of two tenants (one stepping
    down, rung-stamped lanes moving to its int8 bucket) beside an
    unledgered third whose lanes go unpriced."""
    from repro.serve.governor import TenantLedger
    if kind == "governor":
        gov = _pin_governor(1.2)
        govs = {"fleet": gov}
    else:
        gov = TenantLedger()
        govs = {"a": gov.add("a", _pin_governor(0.9)),
                "bb": gov.add("bb", _pin_governor(50.0))}
    b = ContinuousBatcher(4, _pin_decode, lambda slot, prompt: len(prompt),
                          eos_id=_PIN_EOS, governor=gov)
    for rid in range(14):
        kw = {}
        if kind == "ledger":
            kw = dict(model=("a", "bb", "c")[(rid // 2) % 3], version=1)
        b.submit(Request(rid=rid, prompt=np.asarray([rid % 5, rid]),
                         max_new_tokens=2 + rid % 4,
                         policy=_PIN_POLICIES[rid % 3],
                         tier=("gold", "bulk")[rid % 2], **kw))
    done = b.run()
    want = _PINNED[kind]
    assert [r.rid for r in done] == want["order"]
    assert {r.rid: r.generated for r in done} == want["generated"]
    assert {r.rid: r.hops for r in done} == want["hops"]
    st = b.stats
    assert (st.total_hops, st.n_events, st.n_priced) == want["stats"][:3]
    assert st.total_pj == pytest.approx(want["stats"][3], rel=1e-6)
    ts = st.tier_summary()
    assert set(ts) == set(want["tiers"])
    for tier, (n_done, n_events, nj) in want["tiers"].items():
        assert (ts[tier]["n_done"], ts[tier]["n_events"]) == (n_done,
                                                              n_events)
        assert ts[tier]["mean_energy_nj"] == pytest.approx(nj, rel=1e-6)
    assert {k: [t[:2] for t in g.transitions]
            for k, g in govs.items()} == want["transitions"]

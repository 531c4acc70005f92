"""Deprecation shims: each legacy entry point must (a) fire a real
``DeprecationWarning`` and (b) still return results identical to the
canonical ``FogEngine.eval(x, key, policy=FogPolicy(...))`` call.

One test per shim — `fog_eval`, `fog_eval_multioutput`, `fog_eval_lazy`,
`fog_ring_eval`, the positional ``eval(x, key, thresh, max_hops)`` form
and `HopMeter` — so a future cleanup that drops a shim (or silences its
warning) fails loudly.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FogEngine, FogPolicy, HopMeter, fog_eval,
                        fog_eval_lazy, fog_eval_multioutput, split)
from repro.core.fog_ring import fog_ring_eval


@pytest.fixture(scope="module")
def gc(trained):
    _, rf = trained
    return split(rf, 2)


@pytest.fixture(scope="module")
def x128(trained):
    ds, _ = trained
    return jnp.asarray(ds.x_test[:128])


def _canonical(gc, x, key, thresh=0.3, lazy=False):
    return FogEngine(gc, lazy=lazy).eval(
        x, key, policy=FogPolicy(threshold=thresh, max_hops=gc.n_groves))


def _assert_same(res, want):
    np.testing.assert_array_equal(np.asarray(res.hops), np.asarray(want.hops))
    np.testing.assert_array_equal(np.asarray(res.label),
                                  np.asarray(want.label))
    np.testing.assert_array_equal(np.asarray(res.proba),
                                  np.asarray(want.proba))


def test_fog_eval_shim_warns_and_matches(gc, x128):
    key = jax.random.key(2)
    with pytest.warns(DeprecationWarning, match="fog_eval is deprecated"):
        res = fog_eval(gc, x128, key, 0.3, gc.n_groves)
    _assert_same(res, _canonical(gc, x128, key))


def test_fog_eval_lazy_shim_warns_and_matches(gc, x128):
    key = jax.random.key(3)
    with pytest.warns(DeprecationWarning,
                      match="fog_eval_lazy is deprecated"):
        res = fog_eval_lazy(gc, x128, key, 0.3, gc.n_groves)
    _assert_same(res, _canonical(gc, x128, key, lazy=True))


def test_fog_eval_multioutput_shim_warns_and_matches(
        trained, rf8_penbased, rf8_noisy_penbased):
    ds, _ = trained
    gcs = (split(rf8_penbased, 2), split(rf8_noisy_penbased, 2))
    x = jnp.asarray(ds.x_test[:96])
    key = jax.random.key(5)
    with pytest.warns(DeprecationWarning,
                      match="fog_eval_multioutput is deprecated"):
        res = fog_eval_multioutput(gcs, x, key, 0.3, 4)
    want = FogEngine(gcs).eval(
        x, key, policy=FogPolicy(threshold=0.3, max_hops=4))
    _assert_same(res, want)


def test_fog_ring_eval_shim_warns_and_matches(gc, x128):
    mesh = jax.make_mesh((1,), ("grove",))
    key = jax.random.key(7)
    with pytest.warns(DeprecationWarning,
                      match="fog_ring_eval is deprecated"):
        proba, hops = fog_ring_eval(gc, x128, key, 0.3, gc.n_groves, mesh)
    want = FogEngine(gc, backend="ring", mesh=mesh).eval(
        x128, key, policy=FogPolicy(threshold=0.3, max_hops=gc.n_groves))
    np.testing.assert_array_equal(np.asarray(hops), np.asarray(want.hops))
    np.testing.assert_array_equal(np.asarray(proba), np.asarray(want.proba))


def test_positional_eval_shim_warns_and_matches(gc, x128):
    key = jax.random.key(11)
    eng = FogEngine(gc)
    with pytest.warns(DeprecationWarning,
                      match=r"eval\(x, key, thresh, max_hops\) is deprecated"):
        res = eng.eval(x128, key, 0.3, max_hops=gc.n_groves)
    _assert_same(res, _canonical(gc, x128, key))


def test_hop_meter_shim_warns_and_matches(gc, x128):
    """HopMeter is redundant with EvalReport telemetry: constructing one
    warns, but the accounting still matches the report's hops."""
    with pytest.warns(DeprecationWarning, match="HopMeter is deprecated"):
        meter = HopMeter()
    res = FogEngine(gc).eval(x128, jax.random.key(2),
                             policy=FogPolicy(threshold=0.3))
    meter.update(res.hops)
    assert meter.n_events == x128.shape[0]
    assert meter.mean_hops == float(np.asarray(res.hops).mean())


def test_canonical_calls_are_warning_free(gc, x128):
    """The replacement forms must not trip any DeprecationWarning."""
    key = jax.random.key(13)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        _canonical(gc, x128, key)
        FogEngine(gc, backend="fused").eval(
            x128, key, policy=FogPolicy(threshold=0.3))
        # the serving path's canonical telemetry is warning-free too
        from repro.serve.scheduler import ContinuousBatcher
        ContinuousBatcher(2, lambda t, l, p: (jnp.zeros((2, 8)), None),
                          lambda slot, prompt: len(prompt))


# --------------------------------------------------------------------------
# launch/roofline -> launch/hlo_cost move (the LM HLO cost model relocated;
# repro.launch.roofline is now the FoG RooflineModel)
# --------------------------------------------------------------------------

def test_roofline_module_shim_warns_and_forwards():
    """Legacy ``from repro.launch.roofline import HloCostModel`` style access
    warns and returns the exact hlo_cost object."""
    import repro.launch.hlo_cost as hc
    import repro.launch.roofline as rl

    for name in ("PEAK_FLOPS", "HBM_BW", "HloCostModel",
                 "analytic_model_flops", "_shape_bytes"):
        with pytest.warns(DeprecationWarning, match=f"{name} moved"):
            got = getattr(rl, name)
        assert got is getattr(hc, name)
    # non-moved garbage still raises AttributeError, not a warning
    with pytest.raises(AttributeError):
        rl.no_such_symbol


def test_roofline_shim_objects_still_work():
    """The forwarded HloCostModel parses HLO identically to the new home."""
    import repro.launch.hlo_cost as hc
    import repro.launch.roofline as rl

    hlo = ("HloModule t\n\nENTRY %main (x: f32[8,8]) -> f32[8,8] {\n"
           "  %x = f32[8,8]{1,0} parameter(0)\n"
           "  ROOT %d = f32[8,8]{1,0} dot(%x, %x), "
           "lhs_contracting_dims={1}, rhs_contracting_dims={0}\n}\n")
    with pytest.warns(DeprecationWarning):
        legacy = rl.HloCostModel(hlo).totals()
    assert legacy == hc.HloCostModel(hlo).totals()


def test_roofline_report_legacy_mode_warns_and_guards_division():
    """The LM dry-run JSONL path in benchmarks.roofline_report is deprecated
    but still callable — now with guarded divisions (chips=0, flops=0)."""
    from benchmarks import roofline_report as rr

    rec = {"arch": "a", "shape": "s", "mesh": "m", "hlo_flops": 0,
           "hlo_bytes": 0, "collective_bytes": 0, "model_flops": 0.0,
           "chips": 0}
    with pytest.warns(DeprecationWarning, match="derive"):
        row = rr.derive(rec)
    assert row["useful_flops_ratio"] == 0.0
    assert row["roofline_fraction"] == 0.0
    with pytest.warns(DeprecationWarning, match="table"):
        lines = rr.table([row])
    assert len(lines) == 3

    # the new engine-roofline entry points are warning-free
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        rr.engine_table(rr.engine_rows("BENCH_engine.json"))

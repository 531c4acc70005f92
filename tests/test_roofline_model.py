"""HLO cost model unit tests against hand-crafted HLO text.

The model moved from ``launch/roofline.py`` (now the FoG-specific
RooflineModel — tested in this file too) to ``launch/hlo_cost.py``.
"""
import numpy as np
import pytest

from repro.launch.hlo_cost import (
    HloCostModel, _shape_bytes, analytic_model_flops, collective_bytes_from_hlo,
)
from repro.launch.roofline import (
    PEAKS, TPU_V5E, MachineSpec, RooflineModel, spec_for,
)

HLO = """\
HloModule test

%fused_inner (p0: f32[128,64]) -> f32[128,64] {
  %p0 = f32[128,64]{1,0} parameter(0)
  ROOT %e = f32[128,64]{1,0} exponential(%p0)
}

%body (arg: (s32[], f32[128,64])) -> (s32[], f32[128,64]) {
  %arg = (s32[], f32[128,64]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[128,64]{1,0} get-tuple-element(%arg), index=1
  %w = f32[64,64]{1,0} constant({...})
  %d = f32[128,64]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %f = f32[128,64]{1,0} fusion(%d), kind=kLoop, calls=%fused_inner
  %ar = f32[128,64]{1,0} all-reduce(%f), replica_groups={}
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[128,64]{1,0}) tuple(%ip, %ar)
}

%cond (arg: (s32[], f32[128,64])) -> pred[] {
  %arg = (s32[], f32[128,64]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[128,64]) -> f32[128,64] {
  %x = f32[128,64]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[128,64]{1,0}) tuple(%z, %x)
  %w = (s32[], f32[128,64]{1,0}) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %out = f32[128,64]{1,0} get-tuple-element(%w), index=1
}
"""


def test_shape_bytes():
    assert _shape_bytes("f32[128,64]{1,0}") == 128 * 64 * 4
    assert _shape_bytes("bf16[2,3]") == 12
    assert _shape_bytes("(f32[4], s32[2])") == 24
    assert _shape_bytes("pred[]") == 1


def test_cost_model_scales_loop_body():
    m = HloCostModel(HLO)
    assert m.entry == "main"
    t = m.totals()
    # dot: 2 * 128*64 * 64 contracted = 1,048,576 flops x 10 trips
    assert t["flops"] == 10 * 2 * 128 * 64 * 64
    # collective: all-reduce result 32 KiB x 10 trips
    assert t["collective_bytes"] == 10 * 128 * 64 * 4
    assert t["collective_by_kind"] == {"all-reduce": 10 * 128 * 64 * 4}


def test_collective_regex_fallback():
    got = collective_bytes_from_hlo(HLO)
    assert got["all-reduce"] == 128 * 64 * 4   # unscaled single-pass parse


def test_analytic_model_flops_train_vs_decode():
    from repro.configs import get_arch
    cfg = get_arch("tinyllama-1.1b")
    train = analytic_model_flops(cfg, "train_4k")
    dec = analytic_model_flops(cfg, "decode_32k")
    assert train > dec * 1000
    # train = 6 * N * D
    from repro.configs.base import param_count
    _, active = param_count(cfg)
    assert abs(train - 6 * active * 256 * 4096) / train < 1e-9


# --------------------------------------------------------------------------
# FoG RooflineModel (the module that now lives at launch/roofline.py)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_pack(request):
    """A tiny packed field: 1 head, 4 groves x 2 trees, depth 3, 4 classes."""
    from repro.forest.pack import ForestPack
    from repro.core.grove import split
    from repro.forest.train import TrainConfig, train_random_forest
    rng = np.random.default_rng(0)
    X = rng.standard_normal((300, 12)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int32) + 2 * (X[:, 1] > 0).astype(np.int32)
    rf = train_random_forest(X, y, 4, TrainConfig(n_trees=8, max_depth=3,
                                                  seed=0))
    return ForestPack.from_groves(split(rf, 2), "fp32")


def test_roofline_fused_moves_fewer_table_bytes(small_pack):
    """The paper's claim, in model form: the fused pin touches the tables
    once while the per-hop loop re-gathers a grove slice per lane per
    iteration — at any realistic batch the per-hop traffic dominates."""
    m = RooflineModel(small_pack, n_features=12, spec=TPU_V5E)
    per_hop = m.estimate("reference", batch=1000, iters=4)
    fused = m.estimate("fused", batch=1000, iters=4, hops_total=1300.0)
    assert fused.bytes_moved < per_hop.bytes_moved
    assert per_hop.bytes_moved >= 1000 * 4 * (small_pack.table_bytes
                                              / small_pack.n_groves)


def test_roofline_dtype_aware_bytes(small_pack):
    """int8 tables move a quarter of the fp32 per-hop table traffic."""
    p8 = small_pack.astype("int8")
    f32 = RooflineModel(small_pack, 12, spec=TPU_V5E).estimate(
        "reference", 100, iters=4)
    i8 = RooflineModel(p8, 12, spec=TPU_V5E).estimate("reference", 100,
                                                      iters=4)
    assert i8.bytes_moved < f32.bytes_moved
    # table term shrinks ~4x; the fp32 row/state terms are shared
    assert p8.table_bytes < small_pack.table_bytes / 2


def test_roofline_bound_and_achieved(small_pack):
    m = RooflineModel(small_pack, 12, spec=TPU_V5E)
    est = m.estimate("reference", 1000, iters=4)
    assert est.bound in ("memory", "compute")
    assert est.ideal_s == max(est.memory_s, est.compute_s) > 0
    # achieved: ideal/measured, clamped-safe on zero/missing measurements
    assert est.achieved(2 * est.ideal_s) == pytest.approx(0.5)
    assert est.achieved(0.0) == 0.0
    assert est.achieved(None) == 0.0
    d = est.to_dict(measured_s=est.ideal_s)
    assert d["bound"] == est.bound
    assert d["achieved_pct"] == pytest.approx(100.0, abs=0.01)


def test_roofline_spec_selection(small_pack):
    """Specs are configurable by device kind or value; slower machines lower
    the roofline (bigger ideal_s); a device kind without published peaks is
    an error, not a default."""
    by_kind = RooflineModel(small_pack, 12, spec="TPU v5 lite")
    assert by_kind.spec is TPU_V5E is spec_for("TPU v5 lite")
    assert PEAKS["TPU v5 lite"].peak_bw == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        RooflineModel(small_pack, 12, spec="cpu")
    custom = MachineSpec("slow", peak_flops=1e9, peak_bw=1e9)
    slow = RooflineModel(small_pack, 12, spec=custom).estimate(
        "fused", 100, iters=4)
    fast = RooflineModel(small_pack, 12, spec=TPU_V5E).estimate(
        "fused", 100, iters=4)
    assert slow.ideal_s > fast.ideal_s
    assert slow.bytes_moved == fast.bytes_moved   # traffic is machine-free


def test_roofline_compaction_cuts_compute_not_bytes(small_pack):
    """Compaction scales the fused compute term with Σ hops; HBM traffic
    is unchanged (state lives in VMEM either way)."""
    m = RooflineModel(small_pack, 12, spec=TPU_V5E)
    off = m.estimate("fused", 1000, iters=4, hops_total=1300.0,
                     compact=False)
    on = m.estimate("fused", 1000, iters=4, hops_total=1300.0, compact=True)
    assert on.flops < off.flops
    assert on.bytes_moved == off.bytes_moved


def test_roofline_row_names_map_to_traffic_class(small_pack):
    """Benchmark rows pass their OWN names ("pallas", "fused-compact",
    "reference-lazy"): the traffic class comes from the name root, the
    estimate reports the full name — so BENCH_engine.json roofline rows
    are labeled by the backend that was actually measured."""
    m = RooflineModel(small_pack, 12, spec=TPU_V5E)
    ref = m.estimate("reference", 1000, iters=4)
    for name in ("pallas", "pallas-chunked", "reference-lazy"):
        est = m.estimate(name, 1000, iters=4)
        assert est.backend == name
        assert est.bytes_moved == ref.bytes_moved    # per-hop traffic
        assert est.flops == ref.flops
    fused = m.estimate("fused", 1000, iters=4, hops_total=1300.0,
                       compact=True)
    named = m.estimate("fused-compact", 1000, iters=4, hops_total=1300.0,
                       compact=True)
    assert named.backend == "fused-compact"
    assert named.bytes_moved == fused.bytes_moved    # one table pin
    assert named.flops == fused.flops

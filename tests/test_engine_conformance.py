"""FogEngine backend conformance: every backend must reproduce the legacy
``fog_eval`` / ``fog_eval_lazy`` results — identical labels AND identical
per-example hop counts (the paper's energy quantity) — for fixed seeds.

The multi-device ring path is covered in test_fog_ring.py (subprocess with
forced host devices); here the ring backend runs on a 1-device mesh, which
exercises the shard_map + ppermute + strided-placement machinery with
multiple groves per shard.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (NO_BUDGET, FogEngine, FogPolicy, ForestPack,
                        fog_eval, fog_eval_lazy, fog_eval_multioutput, split)


THRESHES = [0.1, 0.3, 1.1]


def _assert_conforms(res, want, *, exact_proba=False):
    np.testing.assert_array_equal(np.asarray(res.hops), np.asarray(want.hops))
    np.testing.assert_array_equal(np.asarray(res.label),
                                  np.asarray(want.label))
    kw = {} if exact_proba else {"rtol": 1e-6, "atol": 1e-7}
    np.testing.assert_allclose(np.asarray(res.proba), np.asarray(want.proba),
                               **kw)


@pytest.fixture(scope="module")
def gc(trained):
    _, rf = trained
    return split(rf, 2)          # 8 groves x 2 trees


@pytest.fixture(scope="module")
def x257(trained):
    # 257 is prime: never divisible by block_b/chunk_b -> exercises both the
    # kernel's dead-lane block padding and the engine's chunk padding
    ds, _ = trained
    return jnp.asarray(ds.x_test[:257])


@pytest.mark.parametrize("thresh", THRESHES)
@pytest.mark.parametrize("backend", ["reference", "pallas", "fused"])
def test_backend_matches_legacy(gc, x257, backend, thresh):
    key = jax.random.key(7)
    want = fog_eval(gc, x257, key, thresh, gc.n_groves)
    res = FogEngine(gc, backend=backend, block_b=64).eval(
        x257, key, thresh, max_hops=gc.n_groves)
    _assert_conforms(res, want)
    lazy = FogEngine(gc, backend=backend, block_b=64, lazy=True).eval(
        x257, key, thresh, max_hops=gc.n_groves)
    want_lazy = fog_eval_lazy(gc, x257, key, thresh, gc.n_groves)
    _assert_conforms(lazy, want_lazy)
    _assert_conforms(lazy, want)     # lazy == fixed-trip, any backend


@pytest.mark.parametrize("thresh", THRESHES)
def test_ring_backend_matches_legacy_on_one_device_mesh(gc, x257, thresh):
    # B must divide the shard count; 1-device mesh accepts the prime batch
    mesh = jax.make_mesh((1,), ("grove",))
    key = jax.random.key(7)
    want = fog_eval(gc, x257, key, thresh, gc.n_groves)
    res = FogEngine(gc, backend="ring", mesh=mesh).eval(
        x257, key, thresh, max_hops=gc.n_groves)
    _assert_conforms(res, want)


@pytest.mark.parametrize("chunk_b", [64, 100])
def test_chunked_eval_matches_unchunked(gc, x257, chunk_b):
    """B % chunk_b != 0: the tail chunk is dead-padded; results must be
    bit-identical to the whole-batch evaluation."""
    key = jax.random.key(3)
    want = fog_eval(gc, x257, key, 0.3, gc.n_groves)
    for backend in ["reference", "pallas", "fused"]:
        res = FogEngine(gc, backend=backend, chunk_b=chunk_b,
                        block_b=32).eval(x257, key, 0.3,
                                         max_hops=gc.n_groves)
        _assert_conforms(res, want)


@pytest.mark.parametrize("backend", ["reference", "pallas", "fused"])
def test_multioutput_matches_legacy(trained, rf8_penbased,
                                    rf8_noisy_penbased, backend):
    ds, _ = trained
    gcs = (split(rf8_penbased, 2), split(rf8_noisy_penbased, 2))
    x = jnp.asarray(ds.x_test[:130])          # 130 % 64 != 0
    key = jax.random.key(11)
    want = fog_eval_multioutput(gcs, x, key, 0.3, 4)
    res = FogEngine(gcs, backend=backend, block_b=64).eval(
        x, key, 0.3, max_hops=4)
    assert res.proba.shape == (130, 2, ds.n_classes)
    assert res.label.shape == (130, 2)
    _assert_conforms(res, want)


def test_unaligned_kernel_block(gc, trained):
    """The old `assert B % block_b == 0` case: a batch smaller than and not
    divisible by the pallas block must work and agree with reference."""
    ds, _ = trained
    x = jnp.asarray(ds.x_test[:37])
    key = jax.random.key(0)
    ref_res = FogEngine(gc).eval(x, key, 0.3)
    pal_res = FogEngine(gc, backend="pallas", block_b=256).eval(x, key, 0.3)
    _assert_conforms(pal_res, ref_res)
    fus_res = FogEngine(gc, backend="fused", block_b=256).eval(x, key, 0.3)
    _assert_conforms(fus_res, ref_res)


def test_default_max_hops_is_n_groves(gc, x257):
    key = jax.random.key(1)
    a = FogEngine(gc).eval(x257, key, 1.1)
    assert (np.asarray(a.hops) == gc.n_groves).all()


def test_engine_rejects_bad_config(gc):
    with pytest.raises(ValueError):
        FogEngine(gc, backend="asic")
    with pytest.raises(ValueError):
        FogEngine(gc, backend="ring")        # no mesh
    mesh = jax.make_mesh((1,), ("grove",))
    with pytest.raises(NotImplementedError):
        FogEngine((gc, gc), backend="ring", mesh=mesh)


def test_fused_rejects_mismatched_head_tables(gc, x257):
    """The fused backend stacks all heads' tables into one VMEM-resident
    launch; heads with different table shapes must be rejected clearly."""
    from repro.core import GroveCollection
    gc2 = GroveCollection(gc.feature, gc.threshold, gc.leaf[..., :-1])
    eng = FogEngine((gc, gc2), backend="fused")
    with pytest.raises(ValueError, match="identical table shapes"):
        eng.eval(x257, jax.random.key(0), policy=FogPolicy(threshold=0.3))


# ---------------------------------------------------------------------------
# FogPolicy: per-lane thresholds and hop budgets — the runtime-knob contract
# ---------------------------------------------------------------------------

def _engine_for(gc, backend):
    if backend == "ring":
        return FogEngine(gc, backend="ring",
                         mesh=jax.make_mesh((1,), ("grove",)))
    return FogEngine(gc, backend=backend, block_b=64)


@pytest.fixture(scope="module")
def x256(trained):
    ds, _ = trained
    return jnp.asarray(ds.x_test[:256])


@pytest.mark.parametrize("backend", ["reference", "pallas", "fused", "ring"])
def test_per_lane_threshold_matches_scalar_evals(gc, x256, backend):
    """The acceptance contract: a batch under [t_lo]*B/2 + [t_hi]*B/2 must
    reproduce, per lane, the labels AND hop counts of two scalar-threshold
    evaluations at t_lo and t_hi (same key -> same start groves)."""
    key = jax.random.key(7)
    B = x256.shape[0]
    t_lo, t_hi = 0.1, 0.6
    tvec = jnp.concatenate([jnp.full((B // 2,), t_lo),
                            jnp.full((B - B // 2,), t_hi)])
    eng = _engine_for(gc, backend)
    mixed = eng.eval(x256, key, policy=FogPolicy(threshold=tvec,
                                                 max_hops=gc.n_groves))
    lo = eng.eval(x256, key, policy=FogPolicy(threshold=t_lo,
                                              max_hops=gc.n_groves))
    hi = eng.eval(x256, key, policy=FogPolicy(threshold=t_hi,
                                              max_hops=gc.n_groves))
    h = B // 2
    np.testing.assert_array_equal(np.asarray(mixed.hops[:h]),
                                  np.asarray(lo.hops[:h]))
    np.testing.assert_array_equal(np.asarray(mixed.hops[h:]),
                                  np.asarray(hi.hops[h:]))
    np.testing.assert_array_equal(np.asarray(mixed.label[:h]),
                                  np.asarray(lo.label[:h]))
    np.testing.assert_array_equal(np.asarray(mixed.label[h:]),
                                  np.asarray(hi.label[h:]))


def test_per_lane_threshold_backend_conformance(gc, x256):
    """reference vs pallas vs ring under one per-lane policy: bit-identical
    labels + hops (the energy quantity is backend-invariant even per-QoS)."""
    key = jax.random.key(13)
    B = x256.shape[0]
    rng = np.random.default_rng(5)
    tvec = jnp.asarray(rng.choice([0.05, 0.2, 0.5, 0.9], size=B), jnp.float32)
    pol = FogPolicy(threshold=tvec, max_hops=gc.n_groves)
    want = _engine_for(gc, "reference").eval(x256, key, policy=pol)
    for backend in ["pallas", "fused", "ring"]:
        res = _engine_for(gc, backend).eval(x256, key, policy=pol)
        _assert_conforms(res, want)


@pytest.mark.parametrize("backend", ["reference", "pallas", "fused", "ring"])
def test_per_lane_hop_budget(gc, x256, backend):
    """A lane's hop count never exceeds its budget, unbudgeted lanes run to
    the max_hops cap at thresh>1, and budgets are backend-conformant."""
    key = jax.random.key(3)
    B = x256.shape[0]
    bvec = jnp.asarray(np.tile([1, 3, NO_BUDGET, 5], B // 4), jnp.int32)
    pol = FogPolicy(threshold=1.1, max_hops=gc.n_groves, hop_budget=bvec)
    res = _engine_for(gc, backend).eval(x256, key, policy=pol)
    hops = np.asarray(res.hops)
    cap = np.minimum(np.asarray(bvec, np.int64), gc.n_groves)
    np.testing.assert_array_equal(hops, cap)   # thresh>1: budget binds exactly
    want = _engine_for(gc, "reference").eval(x256, key, policy=pol)
    _assert_conforms(res, want)


def test_budget_with_confidence_gate_backend_conformance(gc, x256):
    """Budget AND confidence gates active at once: whichever fires first
    kills the lane; all backends must agree bit-for-bit."""
    key = jax.random.key(11)
    B = x256.shape[0]
    bvec = jnp.where(jnp.arange(B) % 2 == 0, 2, NO_BUDGET).astype(jnp.int32)
    pol = FogPolicy(threshold=0.3, max_hops=gc.n_groves, hop_budget=bvec)
    want = _engine_for(gc, "reference").eval(x256, key, policy=pol)
    assert (np.asarray(want.hops)[::2] <= 2).all()
    unbudgeted = _engine_for(gc, "reference").eval(
        x256, key, policy=FogPolicy(threshold=0.3, max_hops=gc.n_groves))
    # odd lanes carry no budget -> identical to the unbudgeted run
    np.testing.assert_array_equal(np.asarray(want.hops)[1::2],
                                  np.asarray(unbudgeted.hops)[1::2])
    for backend in ["pallas", "fused", "ring"]:
        res = _engine_for(gc, backend).eval(x256, key, policy=pol)
        _assert_conforms(res, want)


@pytest.mark.parametrize("chunk_b", [64, 100])
def test_chunked_per_lane_policy_tail_padding(gc, x257, chunk_b):
    """B=257 is prime: the tail chunk is dead-padded and the per-lane
    threshold/budget vectors must be padded alongside x — results must be
    bit-identical to the unchunked whole-batch evaluation."""
    key = jax.random.key(9)
    B = x257.shape[0]
    tvec = jnp.where(jnp.arange(B) < B // 2, 0.1, 0.6)
    bvec = jnp.where(jnp.arange(B) % 3 == 0, 2, NO_BUDGET).astype(jnp.int32)
    pol = FogPolicy(threshold=tvec, max_hops=gc.n_groves, hop_budget=bvec)
    want = FogEngine(gc).eval(x257, key, policy=pol)
    for backend in ["reference", "pallas", "fused"]:
        res = FogEngine(gc, backend=backend, chunk_b=chunk_b,
                        block_b=32).eval(x257, key, policy=pol)
        _assert_conforms(res, want)


def test_multioutput_per_lane_policy(trained, rf8_penbased,
                                     rf8_noisy_penbased):
    """Per-lane thresholds compose with the min-over-outputs rule."""
    ds, _ = trained
    gcs = (split(rf8_penbased, 2), split(rf8_noisy_penbased, 2))
    x = jnp.asarray(ds.x_test[:128])
    key = jax.random.key(17)
    tvec = jnp.where(jnp.arange(128) < 64, 0.1, 0.5)
    pol = FogPolicy(threshold=tvec, max_hops=4)
    want = FogEngine(gcs).eval(x, key, policy=pol)
    res = FogEngine(gcs, backend="pallas", block_b=64).eval(x, key,
                                                            policy=pol)
    _assert_conforms(res, want)
    fused = FogEngine(gcs, backend="fused", block_b=64).eval(x, key,
                                                             policy=pol)
    _assert_conforms(fused, want)
    lo = FogEngine(gcs).eval(x, key, policy=FogPolicy(threshold=0.1,
                                                      max_hops=4))
    np.testing.assert_array_equal(np.asarray(want.hops[:64]),
                                  np.asarray(lo.hops[:64]))


# ---------------------------------------------------------------------------
# ForestPack precision axis: every backend evaluates packed fp32/bf16/int8
# tables; fp32/bf16 reproduce the legacy results bit-exactly, int8 stays
# within the quantization gates and is backend-conformant with itself.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "pallas", "fused", "ring"])
def test_fp32_pack_bit_identical_to_legacy(gc, x256, backend):
    """fp32 packs store the training arrays verbatim: hops, labels and
    probabilities must equal the legacy path bit-for-bit on every backend."""
    key = jax.random.key(7)
    want = FogEngine(gc).eval(
        x256, key, policy=FogPolicy(threshold=0.3, max_hops=gc.n_groves))
    pol = FogPolicy(threshold=0.3, max_hops=gc.n_groves, precision="fp32")
    res = _engine_for(gc, backend).eval(x256, key, policy=pol)
    _assert_conforms(res, want)


@pytest.mark.parametrize("backend", ["reference", "pallas", "fused", "ring"])
def test_bf16_cross_backend_bit_identical_and_near_fp32(gc, x256, backend):
    """Every backend dequantizes the SAME bf16 pack to the same fp32
    values: hops/labels/proba agree bit-for-bit across backends.  Against
    fp32, bf16 rounding (~2^-8 relative on leaves) shifts margins by up to
    ~2e-3, so lanes sitting that close to the confidence gate or an argmax
    tie may flip — >= 97% of hops and labels must still match."""
    key = jax.random.key(7)
    pol = FogPolicy(threshold=0.3, max_hops=gc.n_groves, precision="bf16")
    want16 = _engine_for(gc, "reference").eval(x256, key, policy=pol)
    res = _engine_for(gc, backend).eval(x256, key, policy=pol)
    _assert_conforms(res, want16)
    want32 = FogEngine(gc).eval(
        x256, key, policy=FogPolicy(threshold=0.3, max_hops=gc.n_groves))
    assert (np.asarray(res.hops)
            == np.asarray(want32.hops)).mean() >= 0.97
    assert (np.asarray(res.label)
            == np.asarray(want32.label)).mean() >= 0.97


def test_int8_cross_backend_bit_identical(gc, x256):
    """All four backends dequantize the SAME int8 pack to the same fp32
    values, so hops, labels and probabilities agree bit-for-bit — the
    energy accounting stays backend-invariant at every precision."""
    key = jax.random.key(13)
    pol = FogPolicy(threshold=0.3, max_hops=gc.n_groves, precision="int8")
    want = _engine_for(gc, "reference").eval(x256, key, policy=pol)
    for backend in ["pallas", "fused", "ring"]:
        res = _engine_for(gc, backend).eval(x256, key, policy=pol)
        _assert_conforms(res, want)


def test_int8_label_agreement_gate(gc, x257, trained):
    """The quantization gate: with every grove voting (no confidence gate
    in play) int8 labels agree with fp32 on >= 99% of examples; under the
    default gated policy, lanes whose margin sits within the quantization
    error of the threshold may flip hops, but labels still agree >= 97%
    and accuracy stays within 1% of fp32 (the CI gate)."""
    ds, _ = trained
    y = ds.y_test[:x257.shape[0]]
    key = jax.random.key(7)
    full = FogPolicy(threshold=1.1, max_hops=gc.n_groves)
    want_f = FogEngine(gc).eval(x257, key, policy=full)
    res_f = FogEngine(gc, precision="int8").eval(x257, key, policy=full)
    agree = (np.asarray(res_f.label) == np.asarray(want_f.label)).mean()
    assert agree >= 0.99, agree

    pol = FogPolicy(threshold=0.3, max_hops=gc.n_groves)
    want = FogEngine(gc).eval(x257, key, policy=pol)
    res = FogEngine(gc, precision="int8").eval(x257, key, policy=pol)
    agree = (np.asarray(res.label) == np.asarray(want.label)).mean()
    assert agree >= 0.97, agree
    acc32 = (np.asarray(want.label) == y).mean()
    acc8 = (np.asarray(res.label) == y).mean()
    assert acc8 >= acc32 - 0.01, (acc8, acc32)


def test_int8_margin_error_bound(gc, x257):
    """Leaf quantization error is grid-bounded: against a hybrid forest
    that walks the SAME paths (int8-dequantized thresholds) but keeps fp32
    leaves, the full-hop int8 probabilities differ by at most half an int8
    grid step, and MaxDiff margins by at most a full step."""
    from repro.core import GroveCollection
    pack = ForestPack.from_groves(gc, "int8")
    feat, thr_dq, leaf_dq = pack.dequantize()
    hybrid = GroveCollection(feat[0], thr_dq[0], gc.leaf)
    key = jax.random.key(3)
    pol = FogPolicy(threshold=1.1, max_hops=gc.n_groves)   # full hops
    want = FogEngine(hybrid).eval(x257, key, policy=pol)
    got = FogEngine(gc, precision="int8").eval(x257, key, policy=pol)
    np.testing.assert_array_equal(np.asarray(got.hops),
                                  np.asarray(want.hops))
    bound = 0.5 * float(np.asarray(pack.leaf_scale).max()) + 1e-6
    err = np.abs(np.asarray(got.proba) - np.asarray(want.proba)).max()
    assert err <= bound, (err, bound)
    from repro.core import maxdiff
    m_got = np.asarray(maxdiff(got.proba))
    m_want = np.asarray(maxdiff(want.proba))
    assert np.abs(m_got - m_want).max() <= 2 * bound


def test_pack_save_load_eval_round_trip(gc, x257, tmp_path):
    """A saved pack reloads to bit-identical tables: every backend's
    evaluation of the loaded pack equals the pre-save evaluation."""
    key = jax.random.key(11)
    pol = FogPolicy(threshold=0.3, max_hops=gc.n_groves)
    for precision in ["fp32", "bf16", "int8"]:
        pack = ForestPack.from_groves(gc, precision)
        path = pack.save(tmp_path / f"pack_{precision}.npz")
        loaded = ForestPack.load(path)
        assert loaded.precision == precision
        want = FogEngine(pack).eval(x257, key, policy=pol)
        for backend in ["reference", "pallas", "fused"]:
            res = FogEngine(loaded, backend=backend,
                            block_b=64).eval(x257, key, policy=pol)
            _assert_conforms(res, want)


def test_auto_chunk_only_when_pack_exceeds_vmem(gc, x256):
    """The fused backend's chunk_b=None/'auto' must NOT chunk a pack that
    fits VMEM (the BENCH_engine fused-chunked regression), and an int8 pack
    of a field whose fp32 pack is over budget must run un-chunked where the
    fp32 evaluation raises the VMEM ValueError."""
    eng = FogEngine(gc, backend="fused")
    small = eng.tables.pack("fp32")
    assert eng._resolve_chunk("fused", small, x256.shape[0], 256, None,
                              x256.shape[1]) is None
    assert eng._resolve_chunk("fused", small, x256.shape[0], 256, "auto",
                              x256.shape[1]) is None
    # explicit chunking is always respected
    assert eng._resolve_chunk("fused", small, 256, 256, 64, 16) == 64

    from repro.core import GroveCollection
    rng = np.random.default_rng(0)
    G, t, depth, C, F, B = 8, 4, 10, 120, 8, 32    # fp32 field ~15.2 MiB
    gc_big = GroveCollection(
        jnp.asarray(rng.integers(0, F, size=(G, t, 2**depth - 1)),
                    jnp.int32),
        jnp.asarray(rng.normal(size=(G, t, 2**depth - 1)), jnp.float32),
        jnp.asarray(rng.dirichlet(np.ones(C), size=(G, t, 2**depth)),
                    jnp.float32))
    x = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
    key = jax.random.key(0)
    pol = FogPolicy(threshold=0.25, max_hops=G)
    big = FogEngine(gc_big, backend="fused", block_b=16)
    with pytest.raises(ValueError, match="usable"):
        big.eval(x, key, policy=pol)               # fp32 tables alone > VMEM
    got = big.eval(x, key, policy=pol.replace(precision="int8"))
    assert big._resolve_chunk(
        "fused", big.tables.pack("int8"), B, 16, None, F) is None
    want = FogEngine(gc_big, precision="int8").eval(x, key, policy=pol)
    _assert_conforms(got, want)


def test_auto_chunk_sizes_from_pack_footprint(gc):
    """When the packed tables fit but the batch block state would push the
    working set over budget, auto-chunking picks the largest lane count
    that fits beside the resident tables and the chunked evaluation matches
    the reference bit-for-bit."""
    from repro.core import GroveCollection
    from repro.kernels.fused_fog import fit_block_b
    rng = np.random.default_rng(1)
    G, t, depth, C, F = 4, 4, 9, 250, 2000         # tables ~7.9 MiB fp32
    gc_mid = GroveCollection(
        jnp.asarray(rng.integers(0, F, size=(G, t, 2**depth - 1)),
                    jnp.int32),
        jnp.asarray(rng.normal(size=(G, t, 2**depth - 1)), jnp.float32),
        jnp.asarray(rng.dirichlet(np.ones(C), size=(G, t, 2**depth)),
                    jnp.float32))
    B = 700
    x = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
    eng = FogEngine(gc_mid, backend="fused", block_b=1024)
    pack = eng.tables.pack("fp32")
    cb = eng._resolve_chunk("fused", pack, B, 1024, None, F)
    fit = fit_block_b(*pack.layout("fused"), n_features=F)
    assert cb is not None and cb <= fit < B
    key = jax.random.key(2)
    pol = FogPolicy(threshold=0.3, max_hops=G)
    want = FogEngine(gc_mid).eval(x, key, policy=pol)
    got = eng.eval(x, key, policy=pol)
    _assert_conforms(got, want)


def test_deprecated_positional_eval_warns_and_matches(gc, x256):
    key = jax.random.key(1)
    eng = FogEngine(gc)
    with pytest.warns(DeprecationWarning):
        legacy = eng.eval(x256, key, 0.3, max_hops=gc.n_groves)
    res = eng.eval(x256, key, policy=FogPolicy(threshold=0.3,
                                               max_hops=gc.n_groves))
    _assert_conforms(res, legacy, exact_proba=True)


def test_policy_and_positional_args_conflict(gc, x256):
    with pytest.raises(TypeError):
        FogEngine(gc).eval(x256, jax.random.key(0), 0.3,
                           policy=FogPolicy())
    with pytest.raises(TypeError):
        FogEngine(gc).eval(x256, jax.random.key(0), FogPolicy(),
                           policy=FogPolicy())


def test_positional_policy_is_canonical(gc, x256):
    """eval(x, key, FogPolicy(...)) — the decode_step_fog calling style —
    must work, warning-free, identical to the keyword form."""
    import warnings as _w
    key = jax.random.key(5)
    pol = FogPolicy(threshold=0.3, max_hops=gc.n_groves)
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)
        res = FogEngine(gc).eval(x256, key, pol)
    want = FogEngine(gc).eval(x256, key, policy=pol)
    _assert_conforms(res, want, exact_proba=True)


# ---------------------------------------------------------------------------
# adversarial fused shapes: prime batches x auto-chunk x int8 under a tiny
# monkeypatched VMEM budget, and engine-level live-lane compaction
# ---------------------------------------------------------------------------

def test_prime_batch_auto_chunk_int8_tiny_vmem(gc, x257, monkeypatch):
    """Prime batch x chunk_b="auto" x int8 with VMEM_BUDGET squeezed until
    the real forest's pack must chunk: the auto-chunker must pick a
    LANE_ALIGN-aligned chunk whose modeled footprint stays under the tiny
    budget, and the chunked+padded evaluation must stay bit-identical to
    the unconstrained reference."""
    import repro.kernels.fused_fog as ff
    import repro.kernels.tree_traverse as tt
    from repro.kernels.fused_fog import (LANE_ALIGN, fit_block_b,
                                         vmem_working_set)

    key = jax.random.key(11)
    pol = FogPolicy(threshold=0.3, max_hops=gc.n_groves, precision="int8",
                    chunk_b="auto")
    want = FogEngine(gc, precision="int8").eval(x257, key,
                                                policy=pol.replace(
                                                    chunk_b=None))

    eng = FogEngine(gc, backend="fused", block_b=256)
    pack = eng.tables.pack("int8")
    tables = pack.layout("fused")
    # a budget that admits the int8 tables plus one 128-lane block, far
    # below B=257
    tiny_budget = vmem_working_set(*tables, block_b=LANE_ALIGN,
                                   n_features=x257.shape[1]) + 1
    # fused_fog imports VMEM_BUDGET by value: patch BOTH module globals
    monkeypatch.setattr(ff, "VMEM_BUDGET", tiny_budget)
    monkeypatch.setattr(tt, "VMEM_BUDGET", tiny_budget)

    fit = fit_block_b(*tables, n_features=x257.shape[1])
    assert 0 < fit < x257.shape[0]
    assert fit % LANE_ALIGN == 0, "auto-chunk fit must be lane-aligned"
    assert vmem_working_set(*tables, block_b=fit,
                            n_features=x257.shape[1]) < tiny_budget
    cb = eng._resolve_chunk("fused", pack, x257.shape[0], 256, "auto",
                            x257.shape[1])
    assert cb == fit

    got = eng.eval(x257, key, policy=pol)
    _assert_conforms(got, want)


@pytest.mark.parametrize("B", [97, 257])
def test_engine_compaction_bit_identical(gc, trained, B):
    """compact on vs off through the full engine path (chunking, padding,
    autotuned block_b) — bit-identical hops, labels and probabilities."""
    ds, _ = trained
    x = jnp.asarray(ds.x_test[:B])
    key = jax.random.key(13)
    pol = FogPolicy(threshold=0.3, max_hops=gc.n_groves)
    off = FogEngine(gc, backend="fused", compact=False).eval(x, key,
                                                             policy=pol)
    on = FogEngine(gc, backend="fused", compact=True).eval(x, key,
                                                           policy=pol)
    _assert_conforms(on, off, exact_proba=True)
    # and via the policy knob, overriding the engine default
    pol_on = pol.replace(compact=True)
    via_pol = FogEngine(gc, backend="fused", compact=False).eval(
        x, key, policy=pol_on)
    _assert_conforms(via_pol, off, exact_proba=True)

"""Per-kernel allclose vs the pure-jnp oracle, across shape/dtype sweeps.

Randomized property sweeps live in test_properties.py (hypothesis-gated).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _random_forest_arrays(rng, t, depth, C, F):
    n_nodes = 2**depth - 1
    feature = rng.integers(0, F, size=(t, n_nodes)).astype(np.int32)
    threshold = rng.normal(size=(t, n_nodes)).astype(np.float32)
    leaf = rng.dirichlet(np.ones(C), size=(t, 2**depth)).astype(np.float32)
    return feature, threshold, leaf


@pytest.mark.parametrize("t,depth,C,F,B", [
    (1, 1, 2, 3, 4),
    (4, 3, 5, 10, 32),
    (8, 6, 10, 64, 128),
    (16, 8, 26, 617, 256),
    (2, 4, 7, 19, 64),
])
def test_tree_traverse_matches_ref(t, depth, C, F, B):
    rng = np.random.default_rng(42 + t)
    feature, threshold, leaf = _random_forest_arrays(rng, t, depth, C, F)
    x = rng.normal(size=(B, F)).astype(np.float32)
    got = ops.tree_traverse(feature, threshold, leaf, x, block_b=min(64, B))
    want = ref.tree_traverse_ref(jnp.asarray(feature), jnp.asarray(threshold),
                                 jnp.asarray(leaf), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("B", [37, 127, 257])
def test_tree_traverse_unaligned_batch(B):
    """B % block_b != 0 (prime batches): the kernel dead-pads the tail block
    and slices back — was a hard `assert B % block_b == 0` before."""
    rng = np.random.default_rng(B)
    feature, threshold, leaf = _random_forest_arrays(rng, 4, 5, 7, 16)
    x = rng.normal(size=(B, 16)).astype(np.float32)
    got = ops.tree_traverse(feature, threshold, leaf, x, block_b=64)
    want = ref.tree_traverse_ref(jnp.asarray(feature), jnp.asarray(threshold),
                                 jnp.asarray(leaf), jnp.asarray(x))
    assert got.shape == (B, 7)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# VMEM budget rejection: oversized forests must raise a clear error, never
# silently miscompile
# ---------------------------------------------------------------------------

def test_tree_traverse_rejects_vmem_oversized_forest():
    """Leaf tables just over the ~16 MB budget: t * 2**d * C * 4 = 15.7 MB
    for t=32, d=12, C=30."""
    from repro.kernels.tree_traverse import tree_traverse_pallas
    t, depth, C, F, B = 32, 12, 30, 8, 128
    feature = jnp.zeros((t, 2**depth - 1), jnp.int32)
    threshold = jnp.zeros((t, 2**depth - 1), jnp.float32)
    leaf = jnp.zeros((t, 2**depth, C), jnp.float32)
    x = jnp.zeros((B, F), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        tree_traverse_pallas(feature, threshold, leaf, x, block_b=128)


def test_fused_fog_rejects_vmem_oversized_field():
    """The fused kernel pins EVERY grove table; the whole field must clear
    the budget (8 groves x 4 trees x 2**10 leaves x 120 classes = 15.7 MB)."""
    from repro.kernels.fused_fog import fused_fog_pallas
    O, G, t, depth, C, F, B = 1, 8, 4, 10, 120, 8, 64
    feature = jnp.zeros((O, G, t, 2**depth - 1), jnp.int32)
    threshold = jnp.zeros((O, G, t, 2**depth - 1), jnp.float32)
    leaf = jnp.zeros((O, G, t, 2**depth, C), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        fused_fog_pallas(feature, threshold, leaf,
                         jnp.zeros((B, F), jnp.float32),
                         jnp.zeros((B,), jnp.int32),
                         jnp.full((B,), 0.3, jnp.float32),
                         jnp.full((B,), 2**31 - 1, jnp.int32),
                         max_hops=G, block_b=64)


def test_fused_fog_matches_engine_reference():
    """Direct kernel-level check on random tables (no trained forest): one
    launch == the reference backend, bit-exact hops."""
    from repro.core.grove import GroveCollection
    from repro.core.engine import FogEngine
    from repro.core.policy import FogPolicy
    rng = np.random.default_rng(21)
    G, t, depth, C, F, B = 6, 3, 4, 5, 12, 83
    feature = rng.integers(0, F, size=(G, t, 2**depth - 1)).astype(np.int32)
    threshold = rng.normal(size=(G, t, 2**depth - 1)).astype(np.float32)
    leaf = rng.dirichlet(np.ones(C), size=(G, t, 2**depth)).astype(np.float32)
    gc = GroveCollection(jnp.asarray(feature), jnp.asarray(threshold),
                         jnp.asarray(leaf))
    x = jnp.asarray(rng.normal(size=(B, F)).astype(np.float32))
    key = jax.random.key(0)
    pol = FogPolicy(threshold=0.25, max_hops=G)
    want = FogEngine(gc).eval(x, key, policy=pol)
    got = FogEngine(gc, backend="fused", block_b=32).eval(x, key, policy=pol)
    np.testing.assert_array_equal(np.asarray(got.hops), np.asarray(want.hops))
    np.testing.assert_array_equal(np.asarray(got.label),
                                  np.asarray(want.label))
    np.testing.assert_allclose(np.asarray(got.proba), np.asarray(want.proba),
                               rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# packed (bf16/int8) tables: in-kernel dequantize must match the dequantize-
# up-front oracle bit-for-bit, and the VMEM rejection must name the remedies
# ---------------------------------------------------------------------------

def _packed_grove(rng, t, depth, C, F, precision):
    from repro.core.grove import GroveCollection
    from repro.forest.pack import ForestPack
    n_nodes = 2**depth - 1
    feature = rng.integers(0, F, size=(1, t, n_nodes)).astype(np.int32)
    threshold = rng.normal(size=(1, t, n_nodes)).astype(np.float32)
    # sprinkle the complete-tree padding sentinel (+inf = always go left)
    threshold[0, :, n_nodes // 2:] = np.inf
    leaf = rng.dirichlet(np.ones(C), size=(1, t, 2**depth)).astype(np.float32)
    gc = GroveCollection(jnp.asarray(feature), jnp.asarray(threshold),
                         jnp.asarray(leaf))
    return ForestPack.from_groves(gc, precision)


@pytest.mark.parametrize("precision", ["bf16", "int8"])
def test_tree_traverse_packed_matches_dequantized_ref(precision):
    rng = np.random.default_rng(17)
    pack = _packed_grove(rng, t=4, depth=5, C=7, F=16, precision=precision)
    x = rng.normal(size=(83, 16)).astype(np.float32)
    got = ops.tree_traverse(pack.feature[0, 0], pack.threshold[0, 0],
                            pack.leaf[0, 0], x,
                            pack.thr_scale[0, 0], pack.leaf_scale[0, 0],
                            block_b=32)
    feat, thr, leaf = pack.dequantize()
    want = ref.tree_traverse_ref(feat[0, 0], thr[0, 0], leaf[0, 0],
                                 jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("precision", ["bf16", "int8"])
def test_fused_fog_packed_matches_reference_backend(precision):
    """One packed launch == the reference backend evaluating the same pack:
    bit-identical hops/labels, equal probabilities."""
    from repro.core.engine import FogEngine
    from repro.core.grove import GroveCollection
    from repro.core.policy import FogPolicy
    from repro.forest.pack import ForestPack
    rng = np.random.default_rng(23)
    G = 6
    feature = rng.integers(0, 12, size=(G, 3, 15)).astype(np.int32)
    threshold = rng.normal(size=(G, 3, 15)).astype(np.float32)
    threshold[:, :, 10:] = np.inf
    leaf = rng.dirichlet(np.ones(5), size=(G, 3, 16)).astype(np.float32)
    gc = GroveCollection(jnp.asarray(feature), jnp.asarray(threshold),
                         jnp.asarray(leaf))
    pack = ForestPack.from_groves(gc, precision)
    x = jnp.asarray(rng.normal(size=(83, 12)).astype(np.float32))
    key = jax.random.key(0)
    pol = FogPolicy(threshold=0.25, max_hops=G)
    want = FogEngine(pack).eval(x, key, policy=pol)
    got = FogEngine(pack, backend="fused", block_b=32).eval(x, key,
                                                            policy=pol)
    np.testing.assert_array_equal(np.asarray(got.hops), np.asarray(want.hops))
    np.testing.assert_array_equal(np.asarray(got.label),
                                  np.asarray(want.label))
    np.testing.assert_allclose(np.asarray(got.proba), np.asarray(want.proba),
                               rtol=1e-6, atol=1e-7)


def test_vmem_rejection_reports_bytes_and_remedies():
    """Satellite contract: the over-budget ValueError states required vs
    available bytes and suggests chunk_b and precision=\"int8\"."""
    from repro.kernels.fused_fog import fused_fog_pallas
    from repro.kernels.tree_traverse import tree_traverse_pallas
    O, G, t, depth, C, F, B = 1, 8, 4, 10, 120, 8, 64
    feature = jnp.zeros((O, G, t, 2**depth - 1), jnp.int32)
    threshold = jnp.zeros((O, G, t, 2**depth - 1), jnp.float32)
    leaf = jnp.zeros((O, G, t, 2**depth, C), jnp.float32)
    with pytest.raises(ValueError) as ei:
        fused_fog_pallas(feature, threshold, leaf,
                         jnp.zeros((B, F), jnp.float32),
                         jnp.zeros((B,), jnp.int32),
                         jnp.full((B,), 0.3, jnp.float32),
                         jnp.full((B,), 2**31 - 1, jnp.int32),
                         max_hops=G, block_b=64)
    msg = str(ei.value)
    for needle in ["MiB", "usable", "chunk_b", 'precision="int8"']:
        assert needle in msg, (needle, msg)
    with pytest.raises(ValueError) as ei:
        tree_traverse_pallas(jnp.zeros((32, 2**12 - 1), jnp.int32),
                             jnp.zeros((32, 2**12 - 1), jnp.float32),
                             jnp.zeros((32, 2**12, 30), jnp.float32),
                             jnp.zeros((B, F), jnp.float32), block_b=64)
    msg = str(ei.value)
    for needle in ["MiB", "usable", 'precision="int8"']:
        assert needle in msg, (needle, msg)


def test_int8_field_fits_where_fp32_does_not():
    """The acceptance scenario: a field whose fp32 tables exceed the VMEM
    budget evaluates un-chunked through the fused kernel once packed int8."""
    from repro.core.engine import FogEngine
    from repro.core.grove import GroveCollection
    from repro.core.policy import FogPolicy
    from repro.kernels.tree_traverse import VMEM_BUDGET
    rng = np.random.default_rng(5)
    G, t, depth, C, F, B = 8, 4, 10, 120, 8, 48
    gc = GroveCollection(
        jnp.asarray(rng.integers(0, F, size=(G, t, 2**depth - 1)),
                    jnp.int32),
        jnp.asarray(rng.normal(size=(G, t, 2**depth - 1)), jnp.float32),
        jnp.asarray(rng.dirichlet(np.ones(C), size=(G, t, 2**depth)),
                    jnp.float32))
    eng = FogEngine(gc, backend="fused", block_b=16)
    assert eng.tables.pack("fp32").table_bytes >= VMEM_BUDGET
    assert eng.tables.pack("int8").table_bytes < VMEM_BUDGET
    x = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
    pol = FogPolicy(threshold=0.25, max_hops=G, precision="int8")
    got = eng.eval(x, jax.random.key(1), policy=pol)
    want = FogEngine(gc).eval(x, jax.random.key(1), policy=pol)
    np.testing.assert_array_equal(np.asarray(got.hops), np.asarray(want.hops))
    np.testing.assert_array_equal(np.asarray(got.label),
                                  np.asarray(want.label))


@pytest.mark.parametrize("B,C", [(4, 2), (32, 10), (256, 26), (128, 7), (64, 1000)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_top2_confidence_matches_ref(B, C, dtype):
    rng = np.random.default_rng(B + C)
    prob = jnp.asarray(rng.dirichlet(np.ones(C), size=B), dtype)
    got = ops.top2_confidence(prob, block_b=min(64, B))
    want = ref.top2_confidence_ref(prob)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-6,
                               atol=1e-6)


def test_top2_confidence_unaligned_batch():
    """B % block_b != 0: zero-padded tail blocks, margins sliced back."""
    rng = np.random.default_rng(9)
    prob = jnp.asarray(rng.dirichlet(np.ones(6), size=45), jnp.float32)
    got = ops.top2_confidence(prob, block_b=16)
    want = ref.top2_confidence_ref(prob)
    assert got.shape == (45,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_top2_handles_ties():
    prob = jnp.asarray([[0.4, 0.4, 0.2], [1.0, 0.0, 0.0], [1 / 3] * 3])
    got = ops.top2_confidence(prob, block_b=3)
    np.testing.assert_allclose(np.asarray(got), [0.0, 1.0, 0.0], atol=1e-7)


@pytest.mark.parametrize("B,C", [(8, 3), (64, 10), (256, 26)])
def test_grove_aggregate_matches_ref(B, C):
    rng = np.random.default_rng(7)
    prob_acc = jnp.asarray(rng.random((B, C)), jnp.float32)
    contrib = jnp.asarray(rng.dirichlet(np.ones(C), size=B), jnp.float32)
    live = jnp.asarray(rng.random(B) > 0.3)
    hops = jnp.asarray(rng.integers(0, 5, B), jnp.int32)
    thresh = jnp.float32(0.15)
    got = ops.grove_aggregate(prob_acc, contrib, live, hops, thresh,
                              block_b=min(64, B))
    want = ref.grove_aggregate_ref(prob_acc, contrib, live, hops, thresh)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), rtol=1e-6, atol=1e-6)


def test_grove_aggregate_unaligned_batch():
    """B that does not divide block_b: the kernel dead-pads the tail block
    and slices back — was a hard assert before the engine unification."""
    rng = np.random.default_rng(3)
    B, C = 37, 5
    prob_acc = jnp.asarray(rng.random((B, C)), jnp.float32)
    contrib = jnp.asarray(rng.dirichlet(np.ones(C), size=B), jnp.float32)
    live = jnp.asarray(rng.random(B) > 0.5)
    hops = jnp.asarray(rng.integers(0, 4, B), jnp.int32)
    got = ops.grove_aggregate(prob_acc, contrib, live, hops,
                              jnp.float32(0.2), block_b=16)
    want = ref.grove_aggregate_ref(prob_acc, contrib, live, hops,
                                   jnp.float32(0.2))
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=1e-6, atol=1e-6)


def test_grove_aggregate_tie_and_dead_lanes():
    """m1 == m2 ties must give margin 0 (keep hopping); dead lanes must not
    accumulate, count hops, or resurrect."""
    prob_acc = jnp.asarray([[0.4, 0.4, 0.2],   # exact tie, live
                            [0.8, 0.1, 0.1],   # confident, live
                            [0.5, 0.5, 0.0]],  # dead lane
                           jnp.float32)
    contrib = jnp.zeros((3, 3), jnp.float32)
    live = jnp.asarray([True, True, False])
    hops = jnp.asarray([0, 0, 2], jnp.int32)
    prob, hops2, live2, margin = ops.grove_aggregate(
        prob_acc, contrib, live, hops, jnp.float32(0.3), block_b=3)
    np.testing.assert_allclose(np.asarray(margin[:2]), [0.0, 0.7], atol=1e-6)
    assert bool(live2[0]) is True        # tie -> margin 0 -> keeps hopping
    assert bool(live2[1]) is False       # confident -> exits
    assert bool(live2[2]) is False       # dead stays dead
    np.testing.assert_array_equal(np.asarray(hops2), [1, 1, 2])
    np.testing.assert_allclose(np.asarray(prob[2]), np.asarray(prob_acc[2]))


from repro.kernels.flash_attention import flash_attention_pallas
from repro.models.layers import flash_attention as flash_jnp


@pytest.mark.parametrize("B,Sq,Sk,H,K,D,Dv,causal", [
    (1, 8, 8, 2, 1, 4, 4, True),
    (2, 64, 64, 4, 2, 16, 16, True),
    (2, 128, 128, 8, 8, 32, 32, True),
    (1, 64, 64, 4, 1, 32, 16, True),    # MQA + Dv != D (MLA-style)
    (2, 64, 64, 4, 2, 16, 16, False),
])
def test_flash_attention_pallas_matches_ref(B, Sq, Sk, H, K, D, Dv, causal):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Sk, K, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Sk, K, Dv)), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=causal, blk_q=32, blk_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_jnp_matches_ref():
    """The pure-JAX blocked path (used in the dry-run) vs the oracle."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, 64, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
    got = flash_jnp(q, k, v, causal=True, blk_q=16, blk_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


from repro.kernels.ssd_chunk import ssd_chunk_pallas


@pytest.mark.parametrize("B,nc,Q,H,P,N", [
    (1, 1, 8, 1, 4, 4),
    (2, 2, 16, 3, 8, 8),
    (1, 4, 32, 5, 16, 16),
    (2, 2, 64, 2, 32, 32),
])
def test_ssd_chunk_matches_ref(B, nc, Q, H, P, N):
    rng = np.random.default_rng(B * 100 + Q)
    xbar = jnp.asarray(rng.normal(size=(B, nc, Q, H, P)), jnp.float32)
    # negative log-decays, like softplus(dt) * (-exp(A_log))
    a = jnp.asarray(-rng.uniform(0.01, 0.5, size=(B, nc, H, Q)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, nc, Q, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, nc, Q, N)), jnp.float32)
    y, st = ssd_chunk_pallas(xbar, a, Bm, Cm)
    y_ref, st_ref = ref.ssd_chunk_ref(xbar, a, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=1e-5, atol=1e-5)


def test_ssd_chunk_consistent_with_mamba_layer():
    """Kernel output plugged into the inter-chunk recurrence must equal the
    pure-jnp ssd_chunked end to end."""
    from repro.models.mamba2 import ssd_chunked
    rng = np.random.default_rng(5)
    B, S, H, P, N, Q = 2, 64, 3, 8, 8, 16
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, size=(B, S, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    y_want, final_want = ssd_chunked(x, dt, A, Bm, Cm, Q)

    nc = S // Q
    a = (dt * A[None, None, :]).reshape(B, nc, Q, H).transpose(0, 1, 3, 2)
    xbar = (x * dt[..., None]).reshape(B, nc, Q, H, P)
    Bc = Bm.reshape(B, nc, Q, N)
    Cc = Cm.reshape(B, nc, Q, N)
    y_diag, states = ssd_chunk_pallas(xbar, a, Bc, Cc)

    # inter-chunk recurrence (same as models/mamba2.py)
    cum = jnp.cumsum(a, axis=-1)
    chunk_decay = jnp.exp(cum[..., -1])
    def step(s_prev, inp):
        st, dec = inp
        return s_prev * dec[:, :, None, None] + st, s_prev
    s0 = jnp.zeros((B, H, P, N), jnp.float32)
    final, prev = jax.lax.scan(step, s0,
                               (states.transpose(1, 0, 2, 3, 4),
                                chunk_decay.transpose(1, 0, 2)))
    prev = prev.transpose(1, 0, 2, 3, 4)
    y_off = jnp.einsum("bcin,bchi,bchpn->bcihp", Cc, jnp.exp(cum), prev)
    y = (y_diag + y_off).reshape(B, S, H, P)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(final), np.asarray(final_want),
                               rtol=1e-4, atol=1e-4)


def test_ssd_chunked_kernel_backend_equivalence():
    """ssd_chunked(use_kernels=True) == jnp path, including final state."""
    from repro.models.mamba2 import ssd_chunked
    rng = np.random.default_rng(11)
    B, S, H, P, N, Q = 2, 64, 4, 8, 8, 16
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, size=(B, S, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    y0, s0 = ssd_chunked(x, dt, A, Bm, Cm, Q, use_kernels=False)
    y1, s1 = ssd_chunked(x, dt, A, Bm, Cm, Q, use_kernels=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# fused-kernel VMEM accounting, interpret resolution, live-lane compaction
# ---------------------------------------------------------------------------

def test_vmem_lane_bytes_accounting():
    """Byte-exact per-lane model in the kernel's lane-major layout:
    double-buffered x^T column (features padded to whole select steps),
    [O, C] probabilities and five per-lane rows (each an 8-row tile), the
    working x^T copy + carried probabilities + six carried lane rows, one
    walk's temporaries, and — with compaction only — two bf16 bytes per
    lane of the [block_b, block_b] permutation and prefix-count matrices."""
    from repro.kernels.fused_fog import vmem_lane_bytes
    from repro.kernels.tree_traverse import walk_lane_bytes
    got = vmem_lane_bytes(n_heads=2, n_classes=10, n_features=16,
                          block_b=256, compact=True)
    xf, oc = 64, 2 * 16
    want = (2 * 4 * (xf + oc + 5 * 8) + 4 * (xf + 2 * oc + 6 * 8)
            + walk_lane_bytes(n_classes=10) + 2 * 2 * 256)
    assert got == want
    # compaction's matrices are the only term that grows with the block
    off = vmem_lane_bytes(n_heads=2, n_classes=10, n_features=16,
                          block_b=256, compact=False)
    assert got - off == 4 * 256


def test_fit_block_b_aligned():
    """fit_block_b returns a whole number of 128-lane rows (the TPU tiles
    the batch axis in lanes), and its modeled footprint is maximal under
    the budget."""
    from repro.kernels.fused_fog import (LANE_ALIGN, fit_block_b,
                                         vmem_working_set)
    from repro.kernels.tree_traverse import VMEM_BUDGET
    rng = np.random.default_rng(5)
    pack = _packed_grove(rng, t=4, depth=5, C=7, F=16, precision="fp32")
    tables = pack.layout("fused")
    fit = fit_block_b(*tables, n_features=16)
    assert fit > 0 and fit % LANE_ALIGN == 0
    assert vmem_working_set(*tables, block_b=fit,
                            n_features=16) < VMEM_BUDGET
    # the next aligned size up must NOT fit (the fit is maximal)
    assert vmem_working_set(*tables, block_b=fit + LANE_ALIGN,
                            n_features=16) >= VMEM_BUDGET


def test_resolve_interpret_derives_from_backend(monkeypatch):
    """None derives from jax.default_backend(): interpreted off-TPU,
    compiled Mosaic on TPU; an explicit bool always wins."""
    import repro.kernels.tree_traverse as tt
    assert tt.resolve_interpret(True) is True
    assert tt.resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert tt.resolve_interpret(None) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert tt.resolve_interpret(None) is False


def test_fused_fog_interpret_default_not_hardcoded(monkeypatch):
    """fused_fog_pallas(interpret=None) must consult the runtime backend —
    the historical interpret=True default would silently serve the
    interpreted kernel on a real TPU.  On this CPU container the derived
    flag is True, and pallas_call must receive exactly that."""
    import repro.kernels.fused_fog as ff
    seen = {}
    real = ff.pl.pallas_call

    def spy(*a, **kw):
        seen["interpret"] = kw.get("interpret")
        return real(*a, **kw)

    monkeypatch.setattr(ff.pl, "pallas_call", spy)
    rng = np.random.default_rng(7)
    pack = _packed_grove(rng, t=3, depth=4, C=5, F=10, precision="fp32")
    x = jnp.asarray(rng.normal(size=(16, 10)).astype(np.float32))
    ff.fused_fog_pallas(*pack.layout("fused")[:3], x,
                        jnp.zeros((16,), jnp.int32),
                        jnp.full((16,), 0.3, jnp.float32),
                        jnp.full((16,), 2**31 - 1, jnp.int32),
                        *pack.layout("fused")[3:], max_hops=1, block_b=16)
    assert seen["interpret"] is True          # derived: CPU container
    ff.fused_fog_pallas(*pack.layout("fused")[:3], x,
                        jnp.zeros((16,), jnp.int32),
                        jnp.full((16,), 0.3, jnp.float32),
                        jnp.full((16,), 2**31 - 1, jnp.int32),
                        *pack.layout("fused")[3:], max_hops=1, block_b=16,
                        interpret=True)
    assert seen["interpret"] is True          # explicit override honored


def test_fused_compaction_bit_identical_kernel_level():
    """Live-lane compaction is a pure relocation: hops AND probabilities
    must be bit-identical with it on vs off, at a prime batch size that
    forces dead-lane padding, across precisions."""
    from repro.core.grove import GroveCollection
    from repro.core.policy import NO_BUDGET
    from repro.forest.pack import ForestPack
    rng = np.random.default_rng(31)
    G, t, depth, C, F, B = 6, 3, 4, 5, 12, 149   # prime B
    feature = rng.integers(0, F, size=(G, t, 2**depth - 1)).astype(np.int32)
    threshold = rng.normal(size=(G, t, 2**depth - 1)).astype(np.float32)
    leaf = rng.dirichlet(np.ones(C), size=(G, t, 2**depth)).astype(np.float32)
    gc = GroveCollection(jnp.asarray(feature), jnp.asarray(threshold),
                         jnp.asarray(leaf))
    x = jnp.asarray(rng.normal(size=(B, F)).astype(np.float32))
    start = jax.random.randint(jax.random.key(2), (B,), 0, G)
    thresh = jnp.full((B,), 0.25, jnp.float32)
    budget = jnp.full((B,), NO_BUDGET, jnp.int32)
    for precision in ("fp32", "int8"):
        pack = ForestPack.from_groves(gc, precision)
        tables = pack.layout("fused")
        p0, h0 = ops.fused_fog(*tables[:3], x, start, thresh, budget,
                               *tables[3:], max_hops=G, block_b=32,
                               compact=False)
        p1, h1 = ops.fused_fog(*tables[:3], x, start, thresh, budget,
                               *tables[3:], max_hops=G, block_b=32,
                               compact=True)
        np.testing.assert_array_equal(np.asarray(h0), np.asarray(h1))
        np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))

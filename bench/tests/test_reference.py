import numpy as np

from harness import reference


def _field(rng, T=4, depth=3, C=5, F=6):
    n_int = (1 << depth) - 1
    feat = rng.integers(0, F, (T, n_int)).astype(np.int32)
    thr = rng.normal(size=(T, n_int)).astype(np.float32)
    thr[:, -2:] = np.inf
    leaf = rng.dirichlet(np.ones(C), size=(T, 1 << depth)).astype(np.float32)
    return feat, thr, leaf


def _walk(feat, thr, leaf, x, g, k):
    """One row, one grove, by a plain loop."""
    depth = int(np.log2(leaf.shape[1]))
    out = np.zeros(leaf.shape[-1])
    for t in range(g * k, (g + 1) * k):
        i = 0
        for _ in range(depth):
            i = 2 * i + 1 + (x[feat[t, i]] > thr[t, i])
        out += leaf[t, i - (len(feat[t]))]
    return out / k


def test_evaluate_matches_a_row_by_row_walk():
    rng = np.random.default_rng(0)
    feat, thr, leaf = _field(rng)
    x = rng.normal(size=(20, 6)).astype(np.float32)
    start = rng.integers(0, 2, 20)
    labels, margins = reference.evaluate(feat, thr, leaf, x, start, 2)
    for r in range(20):
        acc = np.zeros(leaf.shape[-1])
        for h in range(2):
            acc += _walk(feat, thr, leaf, x[r], (start[r] + h) % 2, 2)
            p = acc / (h + 1)
            s = np.sort(p)
            assert labels[r, h] == np.argmax(p)
            assert np.isclose(margins[r, h], s[-1] - s[-2])


def test_gate_stops_at_the_first_confident_hop():
    margins = np.array([[0.1, 0.5, 0.9], [0.1, 0.2, 0.25], [0.3, 0, 0]])
    labels = np.array([[1, 2, 3], [4, 5, 6], [7, 8, 9]])
    lab, hops = reference.gate(labels, margins, 0.3)
    assert hops.tolist() == [2, 3, 1] and lab.tolist() == [2, 6, 7]


def test_off_edge_spares_only_the_knife_edge():
    margins = np.array([[0.3 + 1e-7, 0.9], [0.2, 0.9], [1e-7, 0.9]])
    labels = np.array([[0, 0], [0, 0], [1, 1]])
    # row 0: reference stops at hop 1 on the edge, served went on: spared;
    # row 1: reference goes on (0.2 < 0.3), served stopped: counted;
    # row 2: reference label 1 at hop... stops at 2, served label differs
    # at hop 2 where the top-2 gap is 0.9: counted
    bad = reference.off_edge([0, 0, 0], [2, 1, 2], labels, margins, 0.3)
    assert bad.tolist() == [False, True, True]
    assert reference.off_edge([0], [0], labels[:1], margins[:1],
                              0.3).tolist() == [True]


def test_int8_grid_matches_the_published_pack():
    import jax.numpy as jnp
    from repro.forest.pack import ForestPack
    rng = np.random.default_rng(1)
    feat, thr, leaf = _field(rng, T=4, depth=4)
    pack = ForestPack._pack(jnp.asarray(feat)[None, :, None],
                            jnp.asarray(thr)[None, :, None],
                            jnp.asarray(leaf)[None, :, None], "int8")
    _, pthr, pleaf = (np.asarray(a) for a in pack.dequantize())
    rthr, rleaf = reference.tables(thr, leaf, "int8")
    np.testing.assert_array_equal(pthr[0, :, 0], rthr)
    np.testing.assert_array_equal(pleaf[0, :, 0], rleaf)


def test_lower_precisions_change_the_tables():
    rng = np.random.default_rng(2)
    _, thr, leaf = _field(rng)
    for p in ("bf16", "int4"):
        t, lf = reference.tables(thr, leaf, p)
        assert np.isinf(t[:, -2:]).all()
        assert not np.array_equal(t[:, :-2], thr[:, :-2])
        assert not np.array_equal(lf, leaf)


def test_start_draws_follow_the_replica_key():
    import jax
    d = reference.start_draws(123, 2, [1, 2, 5], 256, 8)
    assert d.shape == (3, 256) and d.min() >= 0 and d.max() < 8
    want = jax.random.randint(jax.random.fold_in(jax.random.key(125), 5),
                              (256,), 0, 8)
    np.testing.assert_array_equal(d[2], np.asarray(want))

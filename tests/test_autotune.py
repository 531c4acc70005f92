"""Fused-kernel autotuner: analytic seeding, measured sweep, cache."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.grove import split
from repro.core.policy import NO_BUDGET
from repro.forest.pack import ForestPack
from repro.forest.train import TrainConfig, train_random_forest
from repro.kernels import autotune
from repro.kernels.fused_fog import LANE_ALIGN, fit_block_b


@pytest.fixture(scope="module")
def tiny():
    """(pack, x, start, thresh, budget) on a small synthetic forest."""
    rng = np.random.default_rng(3)
    X = rng.standard_normal((200, 10)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int32) + 2 * (X[:, 2] > 0).astype(np.int32)
    rf = train_random_forest(X, y, 4, TrainConfig(n_trees=8, max_depth=4,
                                                  seed=0))
    gc = split(rf, 2)
    pack = ForestPack.from_groves(gc, "fp32")
    B = 96
    x = jnp.asarray(X[:B])
    start = jax.random.randint(jax.random.key(0), (B,), 0, gc.n_groves)
    thresh = jnp.full((B,), 0.3, jnp.float32)
    budget = jnp.full((B,), NO_BUDGET, jnp.int32)
    return pack, x, start, thresh, budget


@pytest.fixture(autouse=True)
def _fresh_cache():
    autotune.clear_cache()
    yield
    autotune.clear_cache()


def test_analytic_seed_is_aligned_and_capped(tiny):
    pack, x, *_ = tiny
    seed = autotune.analytic_block_b(pack, x.shape[1])
    assert seed % LANE_ALIGN == 0
    assert LANE_ALIGN <= seed <= autotune.SEED_CAP
    fit = fit_block_b(*pack.layout("fused"), n_features=x.shape[1])
    assert seed <= max(fit, LANE_ALIGN)


def test_best_config_untuned_returns_analytic(tiny):
    pack, x, *_ = tiny
    cfg = autotune.best_config(pack, x.shape[1])
    assert cfg.source == "analytic"
    assert cfg.measured_s is None
    assert cfg.block_b == autotune.analytic_block_b(pack, x.shape[1])


def test_tune_measures_and_caches(tiny):
    pack, x, start, thresh, budget = tiny
    won = autotune.tune(pack, x, start, thresh, budget,
                        max_hops=pack.n_groves, repeats=1,
                        blocks=[128, 256], persist=False)
    assert won.source == "measured"
    assert won.measured_s > 0
    assert won.block_b in (128, 256)
    # the engine-facing lookup now returns the measured winner
    hit = autotune.best_config(pack, x.shape[1])
    assert hit == won
    # a different field signature is unaffected
    other = autotune.best_config(pack, x.shape[1] + 1)
    assert other.source == "analytic"


def test_candidate_blocks_aligned_and_descending(tiny):
    pack, x, *_ = tiny
    blocks = autotune.candidate_blocks(pack, x.shape[1], int(x.shape[0]))
    assert blocks, "feasible pack must yield candidates"
    assert all(b % LANE_ALIGN == 0 for b in blocks)
    assert blocks == sorted(blocks, reverse=True)
    assert blocks[-1] >= LANE_ALIGN


def test_cache_file_roundtrip(tiny, tmp_path, monkeypatch):
    pack, x, start, thresh, budget = tiny
    path = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    won = autotune.tune(pack, x, start, thresh, budget,
                        max_hops=pack.n_groves, repeats=1, blocks=[128])
    saved = json.loads(path.read_text())
    assert len(saved) == 1
    (cfg,) = saved.values()
    assert cfg["block_b"] == won.block_b and cfg["compact"] == won.compact
    # a fresh process (cleared in-memory cache) reloads the file winner
    autotune.clear_cache()
    hit = autotune.best_config(pack, x.shape[1])
    assert hit.source == "cache-file"
    assert (hit.block_b, hit.compact) == (won.block_b, won.compact)


def test_cache_is_keyed_by_device_kind(tiny, tmp_path, monkeypatch):
    """A winner measured on one kind of device is never served on another,
    and a cache-file entry that names no device kind is ignored."""
    pack, x, start, thresh, budget = tiny
    path = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    won = autotune.tune(pack, x, start, thresh, budget,
                        max_hops=pack.n_groves, repeats=1, blocks=[128])
    key = autotune.pack_key(pack, x.shape[1])
    assert key[0] == jax.devices()[0].device_kind
    saved = json.loads(path.read_text())
    (kstr,) = saved
    # the same signature measured elsewhere, plus a legacy kind-less entry
    other = kstr.replace(key[0], "TPU v5 lite", 1)
    legacy = kstr.split("/", 1)[1]
    saved[other] = dict(saved[kstr], block_b=256, measured_s=1e-9)
    saved[legacy] = dict(saved[kstr], block_b=384, measured_s=1e-9)
    del saved[kstr]
    path.write_text(json.dumps(saved))
    autotune.clear_cache()
    hit = autotune.best_config(pack, x.shape[1])
    assert hit.source == "analytic"       # neither foreign entry is served
    monkeypatch.setattr(autotune, "device_kind", lambda: "TPU v5 lite")
    autotune.clear_cache()
    hit = autotune.best_config(pack, x.shape[1])
    assert (hit.source, hit.block_b) == ("cache-file", 256)
    assert won.block_b == 128


def test_hist_analytic_seed_scatter_on_interpret():
    """On an interpreted backend (CPU CI) the analytic histogram seed must
    be scatter-everywhere: matmul_max_r == 0, runnable tile sizes."""
    from repro.kernels.tree_traverse import resolve_interpret
    cfg = autotune.analytic_hist_config(8, 6, 16, 17, 10)
    assert cfg.source == "analytic" and cfg.measured_s is None
    assert cfg.block_n > 0 and cfg.block_r > 0 and cfg.block_f >= 1
    if resolve_interpret(None):
        assert cfg.matmul_max_r == 0
    # untuned lookup answers immediately with the seed
    assert autotune.best_hist_config(8, 6, 16, 17, 10) == cfg


def test_hist_tune_measures_and_caches():
    won = autotune.tune_histogram(2, 3, 4, 5, 3, n_samples=256, repeats=1,
                                  persist=False)
    assert won.source == "measured"
    assert won.measured_s > 0
    hit = autotune.best_hist_config(2, 3, 4, 5, 3)
    assert hit == won
    # a different trainer signature still gets the analytic seed
    assert autotune.best_hist_config(2, 4, 4, 5, 3).source == "analytic"


def test_cache_file_mixed_fused_and_hist_entries(tiny, tmp_path,
                                                 monkeypatch):
    """One cache file holds both entry kinds; each reloads as its own
    config type keyed by its own signature."""
    pack, x, start, thresh, budget = tiny
    path = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    fused = autotune.tune(pack, x, start, thresh, budget,
                          max_hops=pack.n_groves, repeats=1, blocks=[128])
    hist = autotune.tune_histogram(2, 3, 4, 5, 3, n_samples=256, repeats=1)
    assert len(json.loads(path.read_text())) == 2
    autotune.clear_cache()                    # "fresh process"
    h = autotune.best_hist_config(2, 3, 4, 5, 3)
    f = autotune.best_config(pack, x.shape[1])
    assert h.source == "cache-file" and f.source == "cache-file"
    assert (h.block_n, h.matmul_max_r) == (hist.block_n, hist.matmul_max_r)
    assert (f.block_b, f.compact) == (fused.block_b, fused.compact)


def test_grow_consults_best_hist_config(ds_penbased, monkeypatch):
    """grow_forest must route its tile sizes through the shared best-config
    table (the same lookup discipline as the serving engine)."""
    from repro.forest.grow import grow_forest

    calls = []
    real = autotune.best_hist_config

    def spy(*args):
        calls.append(args)
        return real(*args)

    monkeypatch.setattr(autotune, "best_hist_config", spy)
    ds = ds_penbased
    grow_forest(ds.x_train[:400], ds.y_train[:400], ds.n_classes,
                TrainConfig(n_trees=2, max_depth=3, seed=0,
                            trainer="device"))
    assert len(calls) == 1
    n_trees, depth, n_features, n_bins, n_classes = calls[0]
    assert (n_trees, depth, n_features, n_classes) == (2, 3, 16,
                                                       ds.n_classes)
    assert n_bins >= 2


def test_engine_consults_autotune_when_block_b_unset(tiny, monkeypatch):
    """FogEngine(block_b=None) + fused must route through best_config."""
    from repro.core.engine import FogEngine

    pack, x, *_ = tiny
    calls = []
    real = autotune.best_config

    def spy(p, f):
        calls.append((p.precision, f))
        return real(p, f)

    monkeypatch.setattr(autotune, "best_config", spy)
    eng = FogEngine(pack, backend="fused")
    assert eng.block_b is None
    eng.eval(x, jax.random.key(0))
    assert calls == [("fp32", x.shape[1])]

"""Compile every Pallas kernel of the forest path for a described TPU v5e.

Nothing runs: the TPU compiler (shipped with libtpu) compiles each kernel
for a chip that is described, not attached, and raises what the chip's
compiler would raise — unsupported gathers, misaligned blocks, VMEM
overflow.  Shapes are the ones ``chip_smoke.py`` drives: the paper's 8x2
field on the penbased signature (F=16, C=10, depth 8) and the isolet-width
field (F=617, C=26, depth 12), fp32 and int8, compaction on and off, at the
largest batch block the VMEM model admits (so the model can never promise
Mosaic more VMEM than it grants).

The topology is described inside a fixture: only the worker that runs
these tests loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_fog import fit_block_b, fused_fog_pallas
from repro.kernels.grove_aggregate import grove_aggregate_pallas
from repro.kernels.histogram import histogram_level_pallas
from repro.kernels.top2_confidence import top2_confidence_pallas
from repro.kernels.tree_traverse import tree_traverse_pallas

# (n_groves, grove_size, depth, n_classes, n_features)
BENCH = (8, 2, 8, 10, 16)
ISOLET = (8, 2, 12, 26, 617)
B = 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, args, sharding):
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
             for a in args]
    return jax.jit(fn).lower(*specs).compile()


def _field(field, dtype, n_heads=1):
    G, t, depth, C, F = field
    N, L = 2**depth - 1, 2**depth
    return (jax.ShapeDtypeStruct((n_heads, G, t, N), jnp.int32),
            jax.ShapeDtypeStruct((n_heads, G, t, N), dtype),
            jax.ShapeDtypeStruct((n_heads, G, t, L, C), dtype),
            jax.ShapeDtypeStruct((n_heads, G, t, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_heads, G, t, 1, 1), jnp.float32))


@pytest.mark.parametrize("compact", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8],
                         ids=["fp32", "int8"])
@pytest.mark.parametrize("field", [BENCH, ISOLET], ids=["bench", "isolet"])
def test_fused_fog_compiles(one_chip, field, dtype, compact):
    G, t, depth, C, F = field
    feat, thr, leaf, ts, ls = _field(field, dtype)
    block_b = fit_block_b(feat, thr, leaf, ts, ls, n_features=F)
    assert block_b > 0

    def run(feat, thr, leaf, ts, ls, x, start, thresh, budget):
        return fused_fog_pallas(feat, thr, leaf, x, start, thresh, budget,
                                ts, ls, max_hops=G, block_b=block_b,
                                compact=compact, interpret=False)

    args = [feat, thr, leaf, ts, ls,
            jax.ShapeDtypeStruct((B, F), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.int32)]
    compiled = _compile(run, args, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8],
                         ids=["fp32", "int8"])
def test_tree_traverse_compiles(one_chip, dtype):
    _, t, depth, C, F = BENCH
    N, L = 2**depth - 1, 2**depth

    def run(feat, thr, leaf, ts, ls, x):
        return tree_traverse_pallas(feat, thr, leaf, x, ts, ls, block_b=256,
                                    interpret=False)

    args = [jax.ShapeDtypeStruct((t, N), jnp.int32),
            jax.ShapeDtypeStruct((t, N), dtype),
            jax.ShapeDtypeStruct((t, L, C), dtype),
            jax.ShapeDtypeStruct((t, 1), jnp.float32),
            jax.ShapeDtypeStruct((t, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, F), jnp.float32)]
    _compile(run, args, one_chip)


def test_grove_aggregate_compiles(one_chip):
    C = BENCH[3]

    def run(prob, contrib, live, hops, thresh):
        return grove_aggregate_pallas(prob, contrib, live, hops, thresh,
                                      block_b=256, interpret=False)

    args = [jax.ShapeDtypeStruct((B, C), jnp.float32),
            jax.ShapeDtypeStruct((B, C), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.float32)]
    _compile(run, args, one_chip)


def test_top2_confidence_compiles(one_chip):
    def run(prob):
        return top2_confidence_pallas(prob, block_b=256, interpret=False)

    _compile(run, [jax.ShapeDtypeStruct((B, BENCH[3]), jnp.float32)],
             one_chip)


@pytest.mark.parametrize("level", [0, 7])
def test_histogram_level_compiles(one_chip, level):
    """The trainer's level histogram at the penbased fit: 16 trees, 4000
    samples, 16 features, 32 bins, 10 classes; level 7 is the deepest level
    the analytic seed sends to the kernel (128 nodes x 10 classes rows)."""
    T, N, F, n_bins, C = 16, 4000, 16, 32, 10

    def run(node, y, w, bins):
        return histogram_level_pallas(node, y, w, bins, n_nodes=1 << level,
                                      n_bins=n_bins, n_classes=C,
                                      interpret=False)

    args = [jax.ShapeDtypeStruct((T, N), jnp.int32),
            jax.ShapeDtypeStruct((N,), jnp.int32),
            jax.ShapeDtypeStruct((T, N), jnp.float32),
            jax.ShapeDtypeStruct((N, F), jnp.uint8)]
    _compile(run, args, one_chip)

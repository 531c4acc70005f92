"""The whole harness, with the timed path broken underneath, reads
``correct`` false: once for each fault a serving cell can have."""
import jax.numpy as jnp
import pytest

import repro.serve.dispatch as dispatch
from tiny import correct, run_tiny

REAL = dispatch._serve_eval_packed


def _altered(*a, **k):
    # an answer altered where it is produced: every 5th lane's label
    nxt, hops, energy, step = REAL(*a, **k)
    lane = jnp.arange(nxt.shape[0])
    return jnp.where(lane % 5 == 0, (nxt + 1) % 3, nxt), hops, energy, step


def _half_left_out(*a, **k):
    # half of the batch left out: every other lane of the span is not
    # evaluated and comes back empty
    nxt, hops, energy, step = REAL(*a, **k)
    keep = jnp.arange(nxt.shape[0]) % 2 == 0
    return (jnp.where(keep, nxt, 0), jnp.where(keep, hops, 0), energy, step)


def _state_unchanged(pack, x, key, step, *a, **k):
    # a step that returns its state unchanged: the dispatch counter that
    # seeds the start-grove draw never advances
    nxt, hops, energy, _ = REAL(pack, x, key, step, *a, **k)
    return nxt, hops, energy, step


@pytest.mark.parametrize("workload", ["pen-8x2.gate.open",
                                      "isolet-8x2.gate.bulk"])
@pytest.mark.parametrize("fault", [_altered, _half_left_out,
                                   _state_unchanged])
def test_fault_is_caught(tmp_path, monkeypatch, fault, workload):
    monkeypatch.setattr(dispatch, "_serve_eval_packed", fault)
    out = run_tiny(tmp_path, workload)
    assert not correct(out), out["checks"]

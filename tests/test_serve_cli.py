"""Serve CLI regressions (launch/serve.py).

The --fog-backend bug: the CLI hardcoded its backend choices and silently
fell out of sync with the engine registry (``ring`` was unreachable).  The
parser now derives choices from ``core.policy.BACKENDS`` / ``PRECISIONS``;
these tests pin that contract so a new backend or precision can never be
un-servable again.  The LM decode path itself is served end to end in
process.
"""
import pytest

from repro.core.policy import BACKENDS, PRECISIONS
from repro.launch.serve import build_parser


def _actions():
    return {a.dest: a for a in build_parser()._actions}


def test_fog_backend_choices_track_engine_registry():
    acts = _actions()
    assert list(acts["fog_backend"].choices) == list(BACKENDS)
    assert "ring" in acts["fog_backend"].choices


def test_fog_precision_choices_track_pack_registry():
    acts = _actions()
    assert list(acts["fog_precision"].choices) == list(PRECISIONS)


def test_data_parallel_knobs_exposed():
    acts = _actions()
    assert acts["devices"].default == 1
    assert acts["max_queue"].default is None
    assert list(acts["shed_policy"].choices) == ["reject", "oldest"]


def test_every_backend_parses():
    ap = build_parser()
    for b in BACKENDS:
        args = ap.parse_args(["--arch", "x", "--fog", "--fog-backend", b])
        assert args.fog_backend == b


@pytest.mark.parametrize("fog", [True, False], ids=["fog", "dense"])
def test_lm_decode_path_serves_end_to_end(fog):
    """The LM decode path, in process on a --smoke arch: one host replica
    per device behind the dispatcher, every request decoded to its token
    budget, and hop telemetry on every token — the early-exit gate's with
    --fog, every grove for the dense step."""
    from repro.configs import smoke_config
    from repro.launch.serve import _serve_lm
    from repro.models.fog_exit import grove_boundaries

    argv = ["--arch", "tinyllama-1.1b", "--smoke", "--requests", "3",
            "--slots", "2", "--max-new", "3"] + (["--fog"] if fog else [])
    b = _serve_lm(build_parser().parse_args(argv))
    n_groves = len(grove_boundaries(smoke_config("tinyllama-1.1b")))
    assert sorted(r.rid for r in b.completed) == [0, 1, 2]
    for r in b.completed:
        assert len(r.generated) == len(r.hops) == 3
        assert all(1 <= h <= n_groves for h in r.hops)
        if not fog:
            assert r.hops == [n_groves] * 3
    assert b.stats.n_events == 9 and b.stats.total_hops == sum(
        sum(r.hops) for r in b.completed)

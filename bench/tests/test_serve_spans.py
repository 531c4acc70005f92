"""The readers of the dispatcher's harvest sub-phases, and the split of
the device's idle time by the program's ``serve.*`` spans."""
import io
import types

import pytest

from conftest import BENCH
from harness import spans, trace
from harness.spec import metric_readers
from tiny import run_tiny

HARVEST = ("harvest_copy_us_per_step.open", "harvest_wait_us_per_step.open")


def _readers():
    return {m.name: m for m in metric_readers() if m.name in HARVEST}


def _run(loop, phase_ns, steps=4):
    return types.SimpleNamespace(
        loop=loop, window=types.SimpleNamespace(phase_ns=phase_ns,
                                                steps=steps))


@pytest.mark.parametrize("name,key", [(HARVEST[0], "harvest_copy"),
                                      (HARVEST[1], "harvest_wait")])
def test_harvest_readers_on_made_up_counters(name, key):
    r = _readers()[name]
    ph = {"harvest": 9000, "harvest_copy": 6000, "harvest_wait": 2000}
    assert r.read(_run("open", ph)) == pytest.approx(ph[key] / 4 / 1e3)
    assert r.read(_run("closed", ph)) is None
    # a program without the sub-phase counters gives nothing
    assert r.read(_run("open", {"harvest": 9000})) is None


def test_a_tiny_run_yields_the_harvest_metrics(tmp_path):
    out = run_tiny(tmp_path, seconds=0.5)
    w = out["window"]
    run = types.SimpleNamespace(loop="open", window=w)
    got = {n: r.read(run) for n, r in _readers().items()}
    assert all(v is not None and v > 0 for v in got.values()), got
    per_step_harvest = w.phase_ns["harvest"] / w.steps / 1e3
    assert sum(got.values()) <= per_step_harvest


def test_an_idle_gap_is_split_across_the_spans_open_in_it():
    step = [(0, 100, "serve.step"), (10, 40, "serve.harvest"),
            (20, 35, "serve.harvest.wait"), (60, 90, "serve.refill")]
    split = spans.split_idle([(30, 70)], step)
    assert split == {"serve.harvest.wait": 5, "serve.harvest": 5,
                     "serve.step": 20, "serve.refill": 10}
    assert sum(split.values()) == 40
    # before and after every span: outside serve
    split = spans.split_idle([(-10, 5), (95, 120)], step)
    assert split == {spans.OUTSIDE: 30, "serve.step": 10}


def test_pieces_name_the_innermost_span():
    assert spans.innermost([(0, 10, "a"), (2, 4, "b"), (4, 12, "c")]) == [
        (0, 2, "a"), (2, 4, "b"), (4, 10, "c")]


def test_a_trace_without_serve_spans_is_all_outside():
    path = BENCH / "testdata" / "tpu_pen_steps.xplane.pb"
    log = io.StringIO()
    split = spans.idle_by_span(path, log=log)
    assert set(split) == {spans.OUTSIDE}
    assert log.getvalue().startswith(f"[idle-by-span] {spans.OUTSIDE} ")
    assert split[spans.OUTSIDE] == pytest.approx(
        sum(trace.summarize(path).idle_by_host.values()))

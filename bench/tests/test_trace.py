"""The trace reduction on a small trace recorded on a TPU v5e
(``bench/testdata``: 0.2 s of ``pen-8x2.gate.open`` traced by
``bench/run.py --trace 1``; the fused kernel runs as ``%fused_fog``
custom calls on the ``XLA Ops`` line of ``/device:TPU:0``)."""
import pytest

from conftest import BENCH
from harness import trace

TRACE = BENCH / "testdata" / "tpu_pen_steps.xplane.pb"


@pytest.fixture(scope="module")
def ops():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(TRACE))
    return [(e.name, e.start_ns, e.duration_ns)
            for p in pd.planes if p.name == "/device:TPU:0"
            for line in p.lines if line.name == "XLA Ops"
            for e in line.events]


def test_busy_is_the_union_of_op_intervals(ops):
    s = trace.summarize(TRACE, kernels=("fused_fog",))
    assert s.n_chips == 1 and ops
    spans = sorted((a, a + d) for _, a, d in ops)
    covered, end = 0.0, -1.0
    for a, b in spans:
        if b > end:
            covered += b - max(a, end)
            end = b
    assert s.busy_s[0] == pytest.approx(covered * 1e-9)
    assert s.busy_s[0] <= sum(d for _, _, d in ops) * 1e-9 + 1e-12


def test_kernel_time_is_its_ops(ops):
    s = trace.summarize(TRACE, kernels=("fused_fog", "no_such_kernel"))
    want = sum(d for name, _, d in ops if "fused_fog" in name) * 1e-9
    assert 0 < want < s.busy_s[0]
    assert s.kernel_seconds["fused_fog"] == pytest.approx(want)
    assert "no_such_kernel" not in s.kernel_seconds
    assert sum(s.op_seconds.values()) == pytest.approx(
        sum(d for _, _, d in ops) * 1e-9)


def test_idle_gaps_are_labelled_by_host_spans():
    s = trace.summarize(TRACE)
    assert s.idle_by_host
    assert any(k.startswith("bench.step") for k in s.idle_by_host)
    assert all(v > 0 for v in s.idle_by_host.values())
    assert trace.top({"a": 1.0, "b": 3.0}, 1) == [["b", 3.0]]

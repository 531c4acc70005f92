"""The metric arithmetic, on made-up counters."""
import types

import numpy as np
import pytest

from harness import layers, roofline
from harness.trace import union

PEAK = roofline.PEAKS["TPU v5 lite"]


def _run(**kw):
    w = types.SimpleNamespace(phase_ns={"refill": 3000, "bookkeep": 1000,
                                        "telemetry": 0, "dispatch": 4000,
                                        "harvest": 6000},
                              steps=2, dispatch_wall=0.5, lag=None)
    base = dict(loop="open", window=w, completed_in_window=5, n_chips=1,
                peak=PEAK, trace=None, trace_window_s=2.0,
                calls={"ops": np.array([1e6, 3e6]),
                       "bytes": np.array([8.19e5, 8.19e5]),
                       "lanes": np.array([10, 30])})
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_host_phase_shares():
    r = _run()
    assert layers.per_step(r, layers.SCHED) == pytest.approx(2.0)
    assert layers.per_request(r, layers.DISPATCH) == pytest.approx(2.0)
    r.window.steps = 0
    assert layers.per_step(r, layers.SCHED) is None


def test_roofline_idle_and_mfu():
    tr = types.SimpleNamespace(kernel_seconds={"fused_fog": 4e-6},
                               busy_s=[0.5, 1.5])
    r = _run(trace=tr)
    # each call: bytes 819 kB at 819 GB/s = 1 us > ops at 197 TFLOP/s
    assert layers.kernel_roofline_pct(r, "fused_fog") == pytest.approx(50.0)
    assert layers.kernel_roofline_pct(r, "other") is None
    assert layers.idle_pct(r) == pytest.approx(50.0)
    assert layers.mfu_pct(r) == pytest.approx(100 * 4e6 / (0.5 * 197e12))


def test_work_counts():
    assert roofline.hop_ops(2, 8, 10) == 2 * 26 + 31
    assert roofline.table_bytes(16, 8, 10, "fp32") == 16 * (
        255 * 8 + 256 * 10 * 4)
    assert roofline.table_bytes(16, 8, 10, "int8") == 16 * (
        255 * 5 + 256 * 10 + 8)
    assert roofline.call_bytes(100, 3, 16) == 100 + 3 * 72
    with pytest.raises(ValueError):
        roofline.peaks("TPU v9")


def test_union_of_intervals():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]

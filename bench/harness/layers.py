"""Arithmetic shared by the per-layer metric readers (``bench/metrics``).

Each reader takes a :class:`harness.cell.Run` and returns a number, or
None where its cell gives it nothing to read.
"""
from __future__ import annotations

import numpy as np

from harness import roofline

SCHED = ("refill", "bookkeep", "telemetry")
DISPATCH = ("dispatch", "harvest")


def phase_us(run, phases) -> float:
    """Host microseconds the batcher spent in ``phases`` during the
    window (its own ``phase_ns`` counters)."""
    return sum(run.window.phase_ns.get(p, 0) for p in phases) / 1e3


def per_step(run, phases):
    return phase_us(run, phases) / run.window.steps if run.window.steps \
        else None


def per_request(run, phases):
    n = run.completed_in_window
    return phase_us(run, phases) / n if n else None


def kernel_roofline_pct(run, kernel: str):
    """Least time of the window's evaluation calls (algorithmic ops at the
    bf16 peak or bytes at HBM bandwidth, whichever is longer, per call)
    over the kernel's summed device time in the trace.  None where no
    device operation carries the kernel's name."""
    if run.trace is None or run.calls is None or not len(run.calls["ops"]):
        return None
    t = run.trace.kernel_seconds.get(kernel, 0.0)
    if t <= 0:
        return None
    least = sum(roofline.least_seconds(o, b, run.peak)
                for o, b in zip(run.calls["ops"], run.calls["bytes"]))
    return 100.0 * least / t


def idle_pct(run):
    if run.trace is None or not run.trace.busy_s or not run.trace_window_s:
        return None
    return 100.0 * (1.0 - float(np.mean(run.trace.busy_s))
                    / run.trace_window_s)


def mfu_pct(run):
    """Algorithmic ops of the window's calls over (wall time of the steps
    that dispatched x chips x bf16 peak)."""
    if run.calls is None or not len(run.calls["ops"]) or \
            run.window.dispatch_wall <= 0:
        return None
    return 100.0 * float(np.sum(run.calls["ops"])) / (
        run.window.dispatch_wall * run.n_chips * run.peak["bf16_flops"])

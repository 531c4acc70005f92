"""Scheduler host time per step (refill + bookkeep + telemetry from the
batcher's phase_ns), open-loop cells."""
from harness.layers import SCHED, per_step

LAYER = "scheduler"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "p50_ms"


def read(run):
    return per_step(run, SCHED) if run.loop == "open" else None

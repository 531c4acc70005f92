"""Scheduler host time per completed request (refill + bookkeep +
telemetry from the batcher's phase_ns), closed-loop cells."""
from harness.layers import SCHED, per_request

LAYER = "scheduler"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "req_per_s"


def read(run):
    return per_request(run, SCHED) if run.loop == "closed" else None

"""Dispatcher host time per step (dispatch + harvest from the batcher's
phase_ns; harvest includes waiting on the device), open-loop cells."""
from harness.layers import DISPATCH, per_step

LAYER = "dispatcher"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "p50_ms"


def read(run):
    return per_step(run, DISPATCH) if run.loop == "open" else None

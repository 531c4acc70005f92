"""Dispatcher host time per completed request (dispatch + harvest from
the batcher's phase_ns; harvest includes waiting on the device),
closed-loop cells."""
from harness.layers import DISPATCH, per_request

LAYER = "dispatcher"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "req_per_s"


def read(run):
    return per_request(run, DISPATCH) if run.loop == "closed" else None

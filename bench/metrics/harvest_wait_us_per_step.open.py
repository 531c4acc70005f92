"""Harvest host time per step spent blocked until the dispatches' results
are ready on the device (the dispatcher's ``phase_ns["harvest_wait"]``),
open-loop cells.  A program without that counter gives nothing."""
from harness.layers import per_step

LAYER = "dispatcher"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "p50_ms"


def read(run):
    if run.loop != "open" or "harvest_wait" not in run.window.phase_ns:
        return None
    return per_step(run, ("harvest_wait",))

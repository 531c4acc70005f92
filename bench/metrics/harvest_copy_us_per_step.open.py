"""Harvest host time per step spent copying the dispatches' results to
the host and scattering them into the batch (the dispatcher's
``phase_ns["harvest_copy"]``), open-loop cells.  A program without that
counter gives nothing."""
from harness.layers import per_step

LAYER = "dispatcher"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "p50_ms"


def read(run):
    if run.loop != "open" or "harvest_copy" not in run.window.phase_ns:
        return None
    return per_step(run, ("harvest_copy",))

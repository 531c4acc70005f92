"""Host time per step the scheduler spends choosing each refill burst's
replica (the batcher's ``phase_ns["route"]``, a part of ``refill``),
open-loop cells on more than one chip.  A program without that counter
gives nothing."""
from harness.layers import per_step

LAYER = "scheduler"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "p50_ms"


def read(run):
    if run.n_chips == 1 or run.loop != "open" or \
            "route" not in run.window.phase_ns:
        return None
    return per_step(run, ("route",))

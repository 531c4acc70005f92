"""Mean occupied lanes per evaluation call in the window (the
dispatcher's own lane lists), closed-loop cells."""
import numpy as np

LAYER = "scheduler"
UNIT = "lanes"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "req_per_s"


def read(run):
    if run.loop != "closed" or run.calls is None or \
            not len(run.calls["lanes"]):
        return None
    return float(np.mean(run.calls["lanes"]))

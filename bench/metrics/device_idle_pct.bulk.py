"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips, closed-loop cells."""
from harness.layers import idle_pct

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "req_per_s"


def read(run):
    return idle_pct(run) if run.loop == "closed" else None

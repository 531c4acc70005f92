"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips, open-loop cells."""
from harness.layers import idle_pct

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "p50_ms"


def read(run):
    return idle_pct(run) if run.loop == "open" else None

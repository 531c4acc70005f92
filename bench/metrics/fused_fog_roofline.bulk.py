"""Share of the fused kernel's roofline: the least time of the window's
evaluation calls (algorithmic ops and bytes, bench/harness/roofline.py)
over the kernel's device time in the trace, closed-loop cells."""
from harness.layers import kernel_roofline_pct

LAYER = "fused kernel"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "req_per_s"


def read(run):
    return kernel_roofline_pct(run, "fused_fog") if run.loop == "closed" \
        else None

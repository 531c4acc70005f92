"""Whole-step share of the chips' bf16 peak: the window's algorithmic
ops over the wall time of the steps that dispatched, closed-loop cells."""
from harness.layers import mfu_pct

LAYER = "whole step"
UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "req_per_s"


def read(run):
    return mfu_pct(run) if run.loop == "closed" else None

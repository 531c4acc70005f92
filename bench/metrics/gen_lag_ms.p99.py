"""99th percentile of how late the load generator submitted a request
(submit time minus due time, host clock), open-loop cells."""
import numpy as np

LAYER = "load generator"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "p50_ms"


def read(run):
    if run.loop != "open" or run.window.lag is None:
        return None
    return float(np.nanpercentile(run.window.lag, 99)) * 1e3

"""How evenly the router spreads device work over the cell's chips: the
least busy chip's busy seconds over the busiest chip's, from the trace
(a chip with no operation in the window reads 0 busy seconds).  Cells on
more than one chip only."""
LAYER = "router"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "p50_ms"


def read(run):
    if run.n_chips == 1 or run.trace is None or not run.trace.busy_s:
        return None
    busy = (list(run.trace.busy_s) + [0.0] * run.n_chips)[:run.n_chips]
    top = max(busy)
    return 100.0 * min(busy) / top if top > 0 else None

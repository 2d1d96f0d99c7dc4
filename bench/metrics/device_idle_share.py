"""Share of the traced window in which no operation ran on a chip,
averaged over the cell's chips (a chip with no work is idle).  A trace
with no operation on any of the cell's chips has nothing to read."""
import trace_reduce


def read(view):
    span = view.hi - view.lo
    if span <= 0 or not any(view.trace["ops"].get(c) for c in view.chips):
        return None
    busy = [trace_reduce.busy(view.trace, c, view.lo, view.hi)
            for c in view.chips]
    return 100.0 * (1.0 - sum(busy) / len(busy) / span)

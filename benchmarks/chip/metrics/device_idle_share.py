"""Device: 1 - device busy (averaged over the cell's chips) over the
in-service time.  Percent."""
from benchmarks.chip import trace as T


def read(run):
    if run.trace is None or run.in_service_s <= 0:
        return None
    busy = sum(T.total(T.busy(d)) for d in run.trace.devices) * 1e-9
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / run.chips / run.in_service_s)

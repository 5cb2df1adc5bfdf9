"""Model step: device busy time (union of operation intervals, summed over
chips) per token processed, in milliseconds."""
from benchmarks.chip import trace as T


def read(run):
    if run.trace is None or not run.tokens_processed:
        return None
    busy = sum(T.total(T.busy(d)) for d in run.trace.devices) * 1e-9
    return 1e3 * busy / run.tokens_processed if busy else None

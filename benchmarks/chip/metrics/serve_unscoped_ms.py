"""Model step: the serve step's ops under no program scope (copies and
other instructions that XLA inserts), in milliseconds a step: the scope
row ``unscoped``, from ``scopes``.  No reading where the trace has no
scoped serve step."""
from benchmarks.chip import scopes


def read(run):
    return scopes.ms_per_step(run, (scopes.UNSCOPED,))

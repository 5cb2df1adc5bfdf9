"""Model step: the serve step's cache traffic, in milliseconds a step:
the scope rows ``kv_write`` (the block's own write of the new position)
and ``layers`` (the layer scan's slices and write-backs, outside every
block scope), from ``scopes``.  No reading where the trace has no scoped
serve step."""
from benchmarks.chip import scopes


def read(run):
    return scopes.ms_per_step(run, ("kv_write", "layers"))

"""Model step: the serve step's ops that read the weights, in
milliseconds a step: the scope rows ``qkv``, ``attn_out``, ``mlp`` and
``unembed``, from ``scopes``.  Its floor on a v5e is the non-embedding
weights' bytes over HBM bandwidth.  No reading where the trace has no
scoped serve step."""
from benchmarks.chip import scopes


def read(run):
    return scopes.ms_per_step(run, ("qkv", "attn_out", "mlp", "unembed"))

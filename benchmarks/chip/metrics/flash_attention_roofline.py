"""Kernels: the least time of the requests' prefill attention
(``work.least_prefill_attention_seconds``: the larger of its causal FLOPs
over peak and its query, key, value and output bytes over HBM bandwidth)
over the summed device time of the ``flash_attention`` kernel's events.
Percent; no reading where the trace holds no such kernel or no request
has a prefill pass."""
from benchmarks.chip import work


def read(run):
    if run.trace is None or not run.served:
        return None
    kernel = sum((e - s) * 1e-9 for d in run.trace.devices
                 for name, s, e in d.ops
                 if name == "flash_attention")
    least = work.least_prefill_attention_seconds(run.cfg, run.served,
                                                 run.peaks)
    if kernel <= 0 or least <= 0:
        return None
    return 100.0 * least / kernel

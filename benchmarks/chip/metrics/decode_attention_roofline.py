"""Kernels: the least time of the attention that the served requests send
through the decode attention (``work.least_decode_attention_seconds``: the
larger of its FLOPs over peak and its K/V bytes over HBM bandwidth,
counted from the requests) over the summed device time of the
``decode_attention`` kernel's events.  Percent; no reading where the trace
holds no such kernel."""
from benchmarks.chip import work


def read(run):
    if run.trace is None or not run.served:
        return None
    kernel = sum((e - s) * 1e-9 for d in run.trace.devices
                 for name, s, e in d.ops
                 if name == "decode_attention")
    if kernel <= 0:
        return None
    least = work.least_decode_attention_seconds(run.cfg, run.served,
                                                run.peaks)
    return 100.0 * least / kernel

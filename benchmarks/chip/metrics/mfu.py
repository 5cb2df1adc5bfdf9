"""Model step: model FLOPs the served requests require
(``work.model_flops``) over in-service seconds x chips x the chip's bf16
peak.  Percent.

In service is the union of the requests' service intervals on the host
clock (for a ``batch`` mix, whose batches run back to back, the whole
window): at a fixed rate the window holds fixed work, so a faster system
would otherwise read a lower share."""
from benchmarks.chip import work


def read(run):
    reqs = run.served
    if not reqs or run.in_service_s <= 0:
        return None
    flops = sum(work.model_flops(run.cfg, r["hist"], r["fed"], len(r["out"]))
                for r in reqs)
    return 100.0 * flops / (run.in_service_s * run.chips
                            * run.peaks["flops_bf16"])

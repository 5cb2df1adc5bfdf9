"""One run of one cell: set-up, a measured window, the check.

The run, in order:

1. Set-up, by the mix's kind (``traffic/<mix>.json``, ``"kind"``):
   ``batch`` (``batch.py``, offline batches through the program's prefill
   and serve steps) or ``sessions`` (``sessions.py``, open-loop sessions
   through ``ServingEngine``).  Weights are made on the device from
   ``--seed``, and every program the window runs is compiled and run once.
2. The window, driven by the kind; nothing compiles inside it.
3. The peak of device memory is read, the program is freed, and the
   float32 reference (``reference``) checks the served tokens that the
   kind hands it (``compared``) against the limits in
   ``limits/<cell>.json``.

With ``trace`` the window runs under the JAX profiler with host
annotations (``bench.*``), and the per-layer metrics are read from the
reduced trace (``trace``, ``metrics/``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import List, Optional

import numpy as np

from . import peaks as peaks_mod
from . import batch, sessions, spec
from . import trace as trace_mod

HERE = Path(__file__).resolve().parent
OUT = HERE / "out"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
KINDS = {"batch": batch, "sessions": sessions}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def process_age() -> float:
    """Seconds since this process started (from /proc, 10 ms ticks)."""
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start / os.sysconf("SC_CLK_TCK")


class CompileCounter:
    """Counts XLA executables built, by compiling or by loading from the
    persistent cache: JAX times both under one event."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1


@dataclasses.dataclass
class Run:
    """What the metric readers see."""
    requests: List[dict]        # the window's requests (turns), in order
    cfg: dict                   # configuration file
    peaks: dict
    chips: int
    in_service_s: float         # union of the requests' service intervals
    trace: Optional[trace_mod.Trace] = None

    @property
    def served(self) -> List[dict]:
        return [t for t in self.requests if t["done"] is not None]

    @property
    def tokens_processed(self) -> int:
        return sum(t["fed"] for t in self.served)


def load_reader(name: str):
    return spec.load(HERE / "metrics" / f"{name}.py").read


def enable_compile_cache(root: Path) -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (devices: {devs}); this benchmark "
                     f"has no CPU fallback")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def percentile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def verdict(limits: dict, widest: Optional[float], malformed: int,
            failed: int, compiles: int):
    """Each compared number beside its limit, and whether all hold."""
    checks = {
        "max_logit_gap": {"value": widest,
                          "limit": limits["max_logit_gap"]},
        "malformed_answers": {"value": malformed, "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
        "compiles_in_window": {"value": compiles, "limit": 0},
    }
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    return checks, correct


def widest_gap(gaps: np.ndarray) -> Optional[float]:
    """The largest gap over the compared positions; None if none."""
    return float(np.nanmax(gaps)) if np.isfinite(gaps).any() else None


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = spec.ROOT, require_tpu: bool = True,
             cell: Optional[dict] = None, log=print) -> dict:
    """One run; returns the result object (the last line's content)."""
    import jax
    cell = cell or spec.cell(name, root)
    w, cfg, mix = cell["workload"], cell["config"], cell["traffic"]
    kind = KINDS[mix["kind"]]
    devices = devices_for(w["chips"], require_tpu)
    dev = devices[0]
    pk = peaks_mod.lookup(dev.device_kind) if require_tpu else {
        "flops_bf16": float("nan"), "hbm_bytes_per_s": float("nan")}
    cache_dir = enable_compile_cache(root) if require_tpu else "off"
    compiles = CompileCounter()
    log(f"devices: {len(jax.devices())} x {dev.device_kind} "
        f"({dev.platform}); cell uses {len(devices)}; compile cache "
        f"{cache_dir}")

    program, kept = kind.prepare(cfg, mix, seed, devices)
    c_setup = compiles.n

    annotate = lambda *a, **k: contextlib.nullcontext()
    tdir = OUT / "trace" / name
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
    setup_s = process_age()            # process start -> window open
    records, facts = kind.serve(program, cfg, mix, seed, seconds, annotate)
    compiled_inside = compiles.n - c_setup
    if trace:
        jax.profiler.stop_trace()

    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    served = [r for r in records if r["done"] is not None]
    failed = len(records) - len(served)
    log(f"set-up {setup_s:.3f} s ({c_setup} programs built); window of "
        f"{seconds} s: {facts['describe']}; {len(served)} requests served, "
        f"{failed} failed, {compiled_inside} programs built inside, ends "
        f"{facts['window_s']:.3f} s")
    log(f"memory: peak_bytes_in_use {[s.get('peak_bytes_in_use') for s in stats]} "
        f"of bytes_limit {[s.get('bytes_limit') for s in stats]}")
    del program
    gc.collect()
    run = Run(requests=records, cfg=cfg, peaks=pk, chips=len(devices),
              in_service_s=facts["in_service_s"])

    t_ref = time.perf_counter()
    tokens, targets, malformed = kind.compared(records, kept, cfg, mix, seed)
    from . import reference
    gaps, _ = reference.gaps(cfg, seed, tokens, targets)
    log(f"reference: {int(np.isfinite(gaps).sum())} served tokens of "
        f"{tokens.shape[0]} sequences compared in "
        f"{time.perf_counter() - t_ref:.3f} s")
    checks, correct = verdict(cell["limits"], widest_gap(gaps), malformed,
                              failed, compiled_inside)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    out = {"correct": correct, "attempted": len(records), "failed": failed}
    values = dict(kind.end_to_end(records, facts, seconds, mix),
                  setup_s=setup_s)
    if trace:
        tr = trace_mod.load(trace_mod.latest_xplane(str(tdir)))
        run.trace = tr
        busy = [trace_mod.busy(d) for d in tr.devices]
        device["busy_s"] = sum(trace_mod.total(b) for b in busy) * 1e-9 / \
            len(devices)
        device["window_s"] = facts["window_s"]
        metrics = {}
        for m in cell["per_layer"]:
            v = load_reader(m["name"])(run)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = breakdown(tr, busy, kind.SPAN)
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell["end_to_end"]}
        out["device"] = device
    log("end to end: " + ", ".join(f"{k} {v:.4f}" for k, v in
                                   sorted(values.items()))
        + f"; in service {run.in_service_s:.3f} s; tokens processed "
        f"{run.tokens_processed}")
    out["compared"] = checks
    return out


def breakdown(tr: trace_mod.Trace, busy, span: str) -> dict:
    ops = sorted(trace_mod.op_seconds(tr).items(), key=lambda x: -x[1])[:10]
    units = [(s, e) for n, s, e, _ in tr.host if n == span]
    lo = units[0][0] if units else tr.start_ns
    hi = units[-1][1] if units else tr.stop_ns
    gaps = trace_mod.gaps(trace_mod.union([iv for b in busy for iv in b]),
                          lo, hi)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[trace_mod.host_span_at(tr, (s + e) / 2),
                           (e - s) * 1e-9] for s, e in gaps]}


def print_result(out: dict) -> None:
    """The compared numbers as the last lines of stderr; the result as the
    last line of stdout."""
    for k, c in out["compared"].items():
        print(f"compared {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)

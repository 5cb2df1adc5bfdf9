"""Reduction of a JAX profiler trace (``.xplane.pb``) to intervals.

Read through ``jax.profiler.ProfileData`` alone.  On a TPU each chip has a
plane ``/device:TPU:<n>`` whose line ``XLA Modules`` holds one event per
program execution and whose line ``XLA Ops`` holds one event per operation
(a Pallas kernel appears as an operation named after its kernel).  Host
annotations (``jax.profiler.TraceAnnotation``) are events on the lines of
the plane ``/host:CPU``.  All times are nanoseconds from the start of the
profile, on one clock for host and device.

A trace recorded on the CPU has no device plane; there the operations run
on host threads and carry an ``hlo_module`` stat, which this reduction
reads as the operations of one device (so that the reduction can be
tested without a chip).
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Tuple

Interval = Tuple[float, float]


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Tuple[str, float, float]]        # (``op_name``, start, end)
    modules: List[Tuple[str, float, float]]    # (module name, start, end)


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Tuple[str, float, float, dict]]  # annotations we asked for
    start_ns: float
    stop_ns: float


def latest_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def op_name(event_name: str) -> str:
    """``%decode_attention.4 = bf16[...] custom-call(...)`` -> the name
    before ``=``, without ``%`` or the numeric suffix."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def load(path: str, host_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host, cpu_ops = [], [], []
    lo, hi = float("inf"), 0.0
    on_tpu = any(p.name.startswith("/device:TPU:") for p in pd.planes)
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                is_op = line.name == "XLA Ops"
                dst = ops if is_op else mods
                for e in line.events:
                    s = e.start_ns
                    dst.append((op_name(e.name) if is_op else e.name, s,
                                s + e.duration_ns))
            devices.append(Device(plane.name, ops, mods))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith(host_prefix):
                        s = e.start_ns
                        host.append((name, s, s + e.duration_ns,
                                     dict(e.stats)))
                    elif not on_tpu and not name.startswith("end: "):
                        stats = dict(e.stats)
                        if "hlo_module" in stats:
                            s = e.start_ns
                            cpu_ops.append((name, s, s + e.duration_ns,
                                            stats))
    if not devices and cpu_ops:
        mods: Dict[Tuple[str, int], List[float]] = {}
        for name, s, t, st in cpu_ops:
            span = mods.setdefault((st["hlo_module"], st.get("run_id")),
                                   [s, t])
            span[0], span[1] = min(span[0], s), max(span[1], t)
        devices.append(Device(
            "/host:CPU", [(op_name(n), s, t) for n, s, t, _ in cpu_ops],
            [(k[0], s, t) for k, (s, t) in mods.items()]))
    for d in devices:
        d.ops.sort(key=lambda x: x[1])
        d.modules.sort(key=lambda x: x[1])
        for _, s, t in d.ops + d.modules:
            lo, hi = min(lo, s), max(hi, t)
    host.sort(key=lambda x: x[1])
    for _, s, t, _ in host:
        lo, hi = min(lo, s), max(hi, t)
    return Trace(devices, host, lo if lo < hi else 0.0, hi)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            if t > out[-1][1]:
                out[-1] = (out[-1][0], t)
        else:
            out.append((s, t))
    return out


def total(intervals: List[Interval]) -> float:
    return sum(t - s for s, t in intervals)


def busy(device: Device) -> List[Interval]:
    """Union of the intervals in which an operation ran on ``device``."""
    return union([(s, t) for _, s, t in device.ops])


def gaps(busy_iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, cur = [], lo
    for s, t in busy_iv:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, t)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, t) for s, t in out if t > s]


def op_seconds(trace: Trace, self_time: bool = True) -> Dict[str, float]:
    """Device seconds by operation name, summed over devices.  An op that
    encloses others (a ``while`` around its body) keeps only its self time
    unless ``self_time`` is False."""
    out: Dict[str, float] = {}
    for d in trace.devices:
        stack: List[list] = []          # [name, end, child seconds]

        def close(entry):
            key = entry[0]
            out[key] = out.get(key, 0.0) + entry[3] - (
                entry[2] if self_time else 0.0)

        for name, s, t in sorted(d.ops, key=lambda x: (x[1], -x[2])):
            while stack and stack[-1][1] <= s:
                close(stack.pop())
            dur = (t - s) * 1e-9
            if stack:
                stack[-1][2] += dur
            stack.append([name, t, 0.0, dur])
        while stack:
            close(stack.pop())
    return out


def host_span_at(trace: Trace, t: float) -> str:
    """The innermost host annotation open at time ``t``."""
    best = None
    for name, s, e, _ in trace.host:
        if s > t:
            break
        if e >= t and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "outside annotations"

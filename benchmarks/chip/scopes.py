"""The serve step's device time by program scope, from a traced run.

The program names each stage of its steps with ``jax.named_scope``
(``SCOPES``; PERF.md, Layers).  XLA keeps the scope path in each
instruction's ``op_name`` metadata, for example
``jit(serve_step)/layers/while/body/attn/kv_write/scatter``.  The v5e
trace, as ``jax.profiler.ProfileData`` reads it, names each op event by its
instruction (``%fusion.12 = bf16[...] fusion(...)``) and gives it no
``op_name`` stat (the event metadata's ``tf_op`` is not exposed).  So the
op names come from the compiled serve step's text: after the window the
serve step is compiled again from the cell's shapes, and each op event is
matched to its instruction by name, numeric suffix included.

The table: each op that runs inside an execution of the serve step (an
``XLA Modules`` event named ``jit_serve_step...``) lands in exactly one
row, the innermost program scope of its ``op_name``.  Ops under ``layers``
and under no block scope form the row ``layers`` (the layer scan's slices
and write-backs); ops with no program scope in their path, such as copies
that XLA inserts, form the row ``unscoped``.  Each op counts its self
time: one that encloses others (the scan's ``while``) keeps only what they
do not cover, as ``trace.op_seconds`` does.  So the rows add up to the
device time of the serve step's ops.
"""
from __future__ import annotations

import dataclasses
import os
import re
import time
from typing import Dict, Iterable, Optional, Tuple

from . import trace as trace_mod

SCOPES = ("embed", "layers", "attn", "mlp", "moe", "rglru", "ssd", "mla",
          "qkv", "kv_write", "attn_kernel", "attn_out", "unembed", "sample",
          "commit")
UNSCOPED = "unscoped"
SERVE_MODULE = "jit_serve_step"

_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%([^\s=]+) = ')
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@dataclasses.dataclass
class Table:
    executions: int                   # serve-step executions traced
    rows: Dict[str, float]            # row -> device seconds (self time)
    ops: Dict[Tuple[str, str], float]  # (row, op name) -> device seconds
    module_s: float                   # summed serve-step module durations

    def ms_per_step(self, rows: Iterable[str]) -> float:
        return 1e3 * sum(self.rows.get(r, 0.0) for r in rows) \
            / self.executions


def row(op_name: str) -> str:
    """The innermost program scope on an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of a compiled module's text; an
    instruction without one maps to ``""``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = op.group(1) if op else ""
    return out


def instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[8] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def serve_events(pd):
    """(module executions, ops inside them) of the first TPU plane, each
    an ``(name, start_ns, end_ns)``."""
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Modules" not in lines or "XLA Ops" not in lines:
            continue
        mods = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in lines["XLA Modules"].events
                       if e.name.startswith(SERVE_MODULE)),
                      key=lambda m: m[1])
        ops = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in lines["XLA Ops"].events),
                     key=lambda o: o[1])
        inside, i = [], 0
        for _, s, t in mods:
            while i < len(ops) and ops[i][1] < s:
                i += 1
            while i < len(ops) and ops[i][1] < t:
                inside.append(ops[i])
                i += 1
        return mods, inside
    return [], []


def table(mods, ops, names: Dict[str, str]) -> Optional[Table]:
    """The scope table of ``serve_events``' result, with op names from
    ``op_names``.  None when an op's instruction is not in ``names`` (the
    text is of another program) or when no op carries a program scope."""
    missing = {instruction(ev) for ev, _, _ in ops} - set(names)
    if missing:
        print(f"scopes: {len(missing)} instructions of the traced serve step "
              f"are not in its compiled text, e.g. {sorted(missing)[:3]}")
        return None
    labelled = [((row(names[instruction(ev)]), trace_mod.op_name(ev)), s, t)
                for ev, s, t in ops]
    by_op = trace_mod.op_seconds(trace_mod.Trace(
        [trace_mod.Device("serve step", labelled, [])], [], 0.0, 0.0))
    rows: Dict[str, float] = {}
    for (r, _), sec in by_op.items():
        rows[r] = rows.get(r, 0.0) + sec
    if not mods or set(rows) <= {UNSCOPED}:
        return None
    return Table(executions=len(mods), rows=rows, ops=by_op,
                 module_s=sum(t - s for _, s, t in mods) * 1e-9)


def serve_step_text(run) -> str:
    """The batch cell's serve step compiled again from its shapes, as the
    run built it (``batch.build``): the same program.  It misses the
    persistent compilation cache, whose key holds the source locations of
    the caller."""
    from repro.configs.shapes import ShapeConfig
    from repro.launch import steps
    from repro.launch.mesh import make_local_mesh
    from . import batch, spec
    first = run.served[0]
    rows = sum(1 for r in run.served if r["batch"] == first["batch"])
    shape = ShapeConfig("decode", len(first["prompt"]) + first["gen"], rows,
                        "decode")
    mesh = make_local_mesh()
    bundle = steps.make_serve_step(spec.model_config(run.cfg), shape, mesh)
    return batch._jit(mesh, bundle).lower(
        *bundle.input_specs).compile().as_text()


def latest_trace() -> Optional[str]:
    from .harness import OUT
    try:
        return trace_mod.latest_xplane(str(OUT / "trace"))
    except FileNotFoundError:
        return None


_tables: Dict[tuple, Optional[Table]] = {}


def read(run) -> Optional[Table]:
    """The scope table of the run's trace, made once per trace file and
    printed with the run's other lines."""
    path = latest_trace() if run.trace is not None else None
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _tables:
        from jax.profiler import ProfileData
        t0 = time.perf_counter()
        mods, ops = serve_events(ProfileData.from_file(path))
        out = table(mods, ops, op_names(serve_step_text(run))) \
            if mods else None
        if out is not None:
            print(f"scopes: table made in {time.perf_counter() - t0:.3f} s"
                  f"\n{format_table(out)}", flush=True)
        _tables[key] = out
    return _tables[key]


def ms_per_step(run, rows: Iterable[str]) -> Optional[float]:
    t = read(run)
    return None if t is None else t.ms_per_step(rows)


def format_table(t: Table) -> str:
    lines = [f"{t.executions} serve-step executions, "
             f"{1e3 * t.module_s / t.executions:.4f} ms a step by module, "
             f"{t.ms_per_step(t.rows):.4f} ms by ops"]
    for r, sec in sorted(t.rows.items(), key=lambda x: -x[1]):
        lines.append(f"{r:12s} {t.ms_per_step([r]):9.4f} ms/step")
        top = sorted(((op, s) for (rr, op), s in t.ops.items() if rr == r),
                     key=lambda x: -x[1])[:6]
        lines += [f"    {op:44s} {1e3 * s / t.executions:9.4f}"
                  for op, s in top]
    return "\n".join(lines)

"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

A cell names a configuration (``configs/<file>``, as listed in
``BENCHMARK.json``) and a traffic mix (``traffic/<mix>.json``); its limits
for ``correct`` sit in ``limits/<cell>.json``; its architecture's pieces in
``families/<model_type>.py``.  Nothing here knows any particular cell or
architecture, so a new one is new files plus one ``workloads`` entry.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
FAMILIES = HERE / "families"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(name: str, root: Path = ROOT) -> dict:
    """The workload entry, its configuration file, traffic mix and limits."""
    bench = load_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(work)}")
    return assemble(work[name], bench, root)


def assemble(w: dict, bench: dict, root: Path = ROOT) -> dict:
    """The pieces of the cell that workload entry ``w`` describes, whether
    or not ``bench`` lists it."""
    name = w["name"]
    conf = {c["name"]: c["file"] for c in bench["configs"]}
    return {
        "workload": w,
        "config": load_json(root / conf[w["config"]]),
        "traffic": load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        "limits": load_json(HERE / "limits" / f"{name}.json"),
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
    }


@functools.lru_cache(maxsize=None)
def load(path: Path):
    """The module at ``path``, loaded by path once per process (a family
    or a metric's reader: neither is a package module)."""
    sp = importlib.util.spec_from_file_location(f"_bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def family(cfg: dict):
    """The architecture's module, ``families/<model_type>.py``, chosen by
    the configuration file's ``model_type`` (the published config's key).

    What is particular to an architecture lives there alone, so that a
    configuration of a new one is ``configs/<name>.json`` plus, where no
    family has it yet, ``families/<model_type>.py``.  A family defines:

    - ``model_config(cfg)``: the program's ``ModelConfig``, after checking
      that the file states nothing the program cannot run;
    - ``stacks(cfg)``: the layers as ``weights.Stack`` entries in model
      order, each a tree key of the program's parameters, a layer count and
      the leaves of one layer (``weights.Leaf``; a leaf may be drawn per
      slice, one key per published index); ``embed_leaves(cfg)``: the
      leaves under ``embed``.  The program's cache leaves are
      ``(layers, batch, positions, ...)`` (``batch.widen_`` pads axis 2),
      under one tree key per stack where there are several;
    - ``layer(cfg, stack, w, x, q8w, q8a)`` and ``logits(cfg, e, x, q8w,
      q8a)``: the float32 reference's layer of stack ``stack`` and its head,
      on float32 weights, with ``q8w`` (per tensor) and ``q8a`` (per row)
      applied to each matrix operand: identity, or fp8 rounding for the
      control; ``reference_rows(cfg)``: the rows a reference layer runs at
      once (None: all);
    - work counts, over every layer, for ``work``: ``matmul_flops(cfg)`` a
      fed position (of experts, the active ones), ``head_flops(cfg)`` a
      generated token, ``attention_flops(cfg, path)`` a query position and
      attended position on ``path`` (``"prefill"`` or ``"decode"``),
      ``cache_bytes(cfg)`` the cache of one position (what decode reads),
      ``prefill_bytes(cfg)`` what the prefill attention reads and writes a
      position.

    An unknown ``model_type`` is an error that lists the families there.
    """
    path = FAMILIES / f"{cfg['model_type']}.py"
    if not path.is_file():
        known = sorted(p.stem for p in FAMILIES.glob("*.py"))
        raise ValueError(f"{cfg['name']}: no family for model_type "
                         f"{cfg['model_type']!r}; {FAMILIES} holds {known}")
    return load(path)


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    return family(cfg).model_config(cfg)

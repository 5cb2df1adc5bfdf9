"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

A cell names a configuration (``configs/<file>``, as listed in
``BENCHMARK.json``) and a traffic mix (``traffic/<mix>.json``); its limits
for ``correct`` sit in ``limits/<cell>.json``.  Nothing here knows any
particular cell, so a new one is new files plus one ``workloads`` entry.
"""
from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


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


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file.

    Only llama-architecture configurations are read here; the file's keys
    use the published config's names.
    """
    import jax.numpy as jnp
    from repro.models.common import ModelConfig
    problems = []
    if cfg["model_type"] != "llama":
        problems.append(f"model_type {cfg['model_type']!r}")
    if cfg["hidden_act"] != "silu" or cfg["torch_dtype"] != "bfloat16":
        problems.append("hidden_act or torch_dtype")
    if problems:
        raise ValueError(f"{cfg['name']}: unsupported {', '.join(problems)}")
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], mlp_variant="swiglu",
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16)

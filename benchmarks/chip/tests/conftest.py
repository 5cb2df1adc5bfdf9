"""Tests of the chip benchmark's own pieces, on the CPU at small sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

(The repository's ``testpaths`` holds only ``tests/``, so a bare
``pytest`` does not collect these.)
"""
import copy
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import spec  # noqa: E402

TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=256,
            serving={"slots_per_row": 8, "max_seq": 128})
TINY_MIX = dict(rate_turns_per_s=3.0,
                first_prompt={"dist": "uniform", "lo": 8, "hi": 16},
                prompt={"dist": "lognormal", "lo": 2, "hi": 12,
                        "median": 4, "sigma": 0.6},
                answer={"dist": "uniform", "lo": 2, "hi": 6},
                drain_seconds=20)
TINY_BATCH = dict(batch=4, prompt=16, answer=16, compare_requests=4, ahead=3)

BATCH_CELL = "deepseek-7b.batch-answers"
# a cell whose pieces are kept under benchmarks/chip/ while BENCHMARK.json
# leaves it out (PERF.md, Open questions)
SESSIONS_CELL = {"name": "deepseek-7b.long-answers", "config": "deepseek-7b",
                 "traffic": "long-answers", "chips": 1}


def tiny_cell(name: str, **cfg_over) -> dict:
    """A cell with its widths and lengths cut to a CPU test size; its
    kind, rows, routing and sessions kept."""
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    listed = {w["name"]: w for w in bench["workloads"]}
    w = listed.get(name) or dict(SESSIONS_CELL, name=name)
    c = copy.deepcopy(spec.assemble(w, bench))
    c["config"].update(TINY, **cfg_over)
    c["traffic"].update(TINY_MIX if c["traffic"]["kind"] == "sessions"
                        else TINY_BATCH)
    return c

#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  Earlier lines of standard output describe the run; the
last is one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics`` and ``device`` (and, with ``--trace 1``, ``breakdown``),
followed by ``compared``: each number the check compared, beside its
limit.  The same numbers are the last lines of standard error.

The run exits non-zero and prints no result when JAX finds no TPU or
fewer chips than the cell asks for, or when the program under test
(``src/repro``) is not in the checkout.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: no program under test at {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    # no TPU runtime logs under a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # the script directory goes: its module names (trace, ...) are not
    # top-level modules
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), root=ROOT)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Read the control of a cell's comparison at the cell's own size.

    python3 benchmarks/chip/control.py --workload dna_chr.build --seeds 1,2,3

The control is the plain reference with the shortcut a faster program
would be tempted by (``harness/reference.py``): suffixes ordered by their
first ``w_max`` symbols only, patterns matched on their first ``w_max``
symbols only.  The cell's loop (``loops/<loop>.py``) puts the control's
output where the window's would be — the texts of a run's first window
builds, or the requests a run's check samples — and the loop's own check
judges it, as it judges a run.  It prints, for each seed, ``correct`` and
each number compared beside its limit.  The benchmark's own runs never run
it; it needs no chip.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

from harness import spec  # noqa: E402
from harness.runs import Run  # noqa: E402


def reading(cell: spec.Cell, seed: int, bench_dir: Path = BENCH_DIR) -> dict:
    """The control's run for ``seed``, judged by the cell's check."""
    loop = spec.load_loop(cell.traffic["loop"], bench_dir)
    run = Run(cell=cell.name, config=cell.config, traffic=cell.traffic,
              seed=seed, n=int(cell.config["n"]))
    loop.control(run)
    loop.check(run)
    return {"correct": all(c.ok for c in run.checks),
            "checks": {c.name: {"value": c.value, "limit": c.limit}
                       for c in run.checks}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, e.g. 1,2,3")
    args = ap.parse_args(argv)
    cell = spec.resolve_cell(spec.load_benchmark(), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": reading(cell, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

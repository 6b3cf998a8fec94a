"""Shared helpers of the chip benchmark's tests: import the harness, and
cut a cell to a size a CPU test run can hold."""

from __future__ import annotations

import copy
import importlib.util
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
for p in (str(BENCH_DIR), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_run_module():
    """``benchmarks/chip/run.py`` as a module (its name is too common to
    import plainly)."""
    spec = importlib.util.spec_from_file_location("chipbench_run",
                                                  BENCH_DIR / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def small_cell(name: str, n: int = 20000):
    """The named cell with its text cut to ``n`` symbols (the memory
    budget re-derived by the configuration's own rule) and its lookup
    traffic cut to CPU size."""
    from harness import spec

    cell = spec.resolve_cell(spec.load_benchmark(ROOT), name)
    cfg = copy.deepcopy(cell.config)
    cfg["n"] = n
    f_max = (n + 1) // (cfg["min_groups"] + 1)
    cfg["era"]["memory_bytes"] = -(-f_max * 2 * 16 * 5 // 3)
    cfg["era"]["r_bytes"] = 16 * (n + 1)
    cell.config = cfg
    if "pool" in cell.traffic:
        tr = copy.deepcopy(cell.traffic)
        tr.update(pool=8192, warm_pool=512, warmup_s=0.2,
                  check_from_first=512, check_sample=128)
        tr.update({k: v for k, v in (("outstanding", 64),
                                     ("rate_per_s", 2000)) if k in tr})
        tr["serve"]["max_batch"] = 32
        tr["limits"]["answers_checked"] = 32
        cell.traffic = tr
    return cell

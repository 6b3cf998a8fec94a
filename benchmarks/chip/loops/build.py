"""``build``: a closed loop of whole builds through
``EraIndexer.build_device``, each ended by ``block_until_ready(dev.ell)``.

Set-up makes the run's texts — the configuration's base text under
distinct relabellings drawn from the seed, one for the warm-up build and
one for each build of the window — and runs the warm-up build, which loads
or compiles every program the window uses (a relabelling keeps every
shape).  The window closes at the end of the first build that ends at or
after ``--seconds``.  No build of the window sees a text that an earlier
build of the run saw.

Check: every window build's leaf array equals the plain reference's
suffix array of its own text, position for position.  Control: the
reference's shortcut (suffixes ordered by their first ``w_max`` symbols
only) in the program's place, on the texts of the window's first builds.
"""

from __future__ import annotations

import math
import time

import numpy as np

from harness import data, reference
from harness.runs import (Check, GcPauses, Run, era_config, obs_begin,
                          obs_end, peak_bytes, program_alphabet)

# texts made in set-up beyond the builds the warm-up's time predicts
SPARE_TEXTS = 2
# relabellings drawn for a run (DNA has only 4! = 24)
MAX_TEXTS = 64


def _one_build(indexer, text) -> dict:
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core.api import BuildReport
    from repro.core.prepare import PrepareStats
    from repro.core.vertical import VerticalStats

    report = BuildReport(VerticalStats(), PrepareStats())
    t0 = time.perf_counter()
    with TraceAnnotation("bench/build_device"):
        dev = indexer.build_device(text, report)
        jax.block_until_ready(dev.ell)
    t1 = time.perf_counter()
    return {"t1": t1, "wall_s": t1 - t0,
            "t_vertical": report.t_vertical, "t_prepare": report.t_prepare,
            "iterations": report.prepare.iterations,
            "ranges": list(report.prepare.ranges),
            "active": list(report.prepare.active_history),
            "ell": np.asarray(dev.ell_host).copy(), "text": text}


class Texts:
    """The run's texts: text 0 is the warm-up's, then one a window build,
    each under a relabelling of its own.  Set-up makes the texts the
    window is expected to need; one beyond them is made when asked for.
    (Only a window of more builds than an alphabet has orders, which
    test-sized texts can reach, meets a relabelling again.)"""

    def __init__(self, run: Run):
        self.base = data.base_text(run.config)
        k = run.alphabet_size
        self.perms = data.permutations(
            k, run.seed, min(MAX_TEXTS, math.factorial(k)))
        self.made: list[np.ndarray] = []

    def make(self, count: int) -> None:
        while len(self.made) < count:
            perm = self.perms[len(self.made) % len(self.perms)]
            self.made.append(data.relabel(self.base, perm))

    def __getitem__(self, i: int) -> np.ndarray:
        self.make(i + 1)
        return self.made[i]


def drive(run: Run, seconds: float, hooks) -> None:
    from repro.core.api import EraIndexer

    indexer = EraIndexer(program_alphabet(run.config), era_config(run.config))
    texts = Texts(run)
    warm = _one_build(indexer, texts[0])
    texts.make(1 + math.ceil(seconds / warm["wall_s"]) + SPARE_TEXTS)
    del warm
    gc_pauses = GcPauses()
    gc_pauses.settle()
    before = obs_begin(hooks)
    compiles0 = run.compiles_setup = hooks.compile_count()
    with hooks.window_ctx():
        t0 = time.perf_counter()
        run.setup_s = t0 - hooks.t_start
        while True:
            run.builds.append(_one_build(indexer, texts[1 + len(run.builds)]))
            if run.builds[-1]["t1"] - t0 >= seconds:
                break
        run.window_s = run.builds[-1]["t1"] - t0
    gc_pauses.done(run)
    run.compiles_in_window = hooks.compile_count() - compiles0
    obs_end(hooks, run, before)
    run.memory_peak_bytes = peak_bytes()
    run.attempted = len(run.builds)
    run.n_leaves = run.n + 1


def check(run: Run) -> None:
    """Every window build's leaf array against the reference suffix array
    of its own text."""
    base = run.alphabet_size + 1
    worst = 0
    for b in run.builds:
        ell = b.pop("ell")
        ref = reference.suffix_array(b.pop("text"), base)
        bad = (int(np.count_nonzero(ell != ref)) if ell.shape == ref.shape
               else len(ref))
        run.failed += bad > 0
        worst = max(worst, bad)
    run.checks.append(Check("leaf_mismatches", worst,
                            run.traffic["limits"]["leaf_mismatches"]))


def control(run: Run, builds: int = 2) -> None:
    """The control's leaf arrays for the texts of the window's first
    ``builds`` builds."""
    base = run.alphabet_size + 1
    depth = int(run.config["era"]["w_max"])
    texts = Texts(run)
    for i in range(1, 1 + builds):
        run.builds.append({"ell": reference.suffix_array(texts[i], base,
                                                         depth),
                           "text": texts[i]})
    run.attempted = len(run.builds)

"""The comparison that decides ``correct`` passes a sound run and fails a
broken one.

Each test drives the rest of a run (everything after the look for a chip)
at a size a CPU test can hold, with the timed path broken underneath, and
sees ``correct`` come out false: a prepare step that returns its state
unchanged, an answer altered where it is produced, half of each served
batch's answers left out.  (A one-chip cell has no exchange between
chips to leave out.)  The control tests put the reference's shortcut —
suffixes ordered and patterns matched on their first 16 symbols — in the
program's place, through ``control.py`` and the loop's own check, and see
``correct`` come out false.
"""

import copy
import importlib.util
import time

import jax.numpy as jnp
import pytest

from chipbench_util import BENCH_DIR, load_run_module, small_cell

RUN = load_run_module()


def _load_control():
    spec = importlib.util.spec_from_file_location("chipbench_control",
                                                  BENCH_DIR / "control.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CONTROL = _load_control()


def _execute(cell, seed=12345):
    return RUN.execute(cell, seed=seed, seconds=0.5, trace=False,
                       t_start=time.perf_counter())


@pytest.mark.parametrize("name", ["dna_chr.build", "swissprot.build",
                                  "dna_chr.seeds"])
def test_sound_run_is_correct(name):
    cell = small_cell(name, n=20000 if "build" in name else 30000)
    res = _execute(cell)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) >= {"setup_s"}


def test_open_loop_run_is_correct():
    """The open loop that ``sweep.py`` drives, on the seeds cell's mix."""
    cell = small_cell("dna_chr.seeds", n=30000)
    cell.traffic = dict(copy.deepcopy(cell.traffic), loop="open_lookup",
                        rate_per_s=2000)
    res = _execute(cell)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1


def test_step_returning_its_state_unchanged_is_caught(monkeypatch):
    from repro.core import prepare

    def frozen(s_padded, states, *args, **kwargs):
        return states, jnp.zeros(states.L.shape[0], jnp.int32)

    monkeypatch.setattr(prepare, "_jit_step_batch", frozen)
    res = _execute(small_cell("dna_chr.build"))
    assert res["correct"] is False
    assert res["checks"]["leaf_mismatches"]["value"] > 0


def test_leaf_altered_where_produced_is_caught(monkeypatch):
    from repro.core import api

    flatten = api._flatten_state

    def swapped(groups, states):
        prefixes, freqs, ell = flatten(groups, states)
        ell = jnp.asarray(ell)
        return prefixes, freqs, ell.at[jnp.array([0, 1])].set(ell[1::-1])

    monkeypatch.setattr(api, "_flatten_state", swapped)
    res = _execute(small_cell("swissprot.build"))
    assert res["correct"] is False
    assert res["checks"]["leaf_mismatches"]["value"] == 2


def _break_consume(monkeypatch, alter):
    from repro.launch.serving import AsyncServer

    consume = AsyncServer._consume

    def broken(self, flight):
        consume(self, flight)
        for i, req in enumerate(flight.requests):
            pos, win = self.results[req.rid]
            self.results[req.rid] = (alter(i, len(flight.requests), pos), win)

    monkeypatch.setattr(AsyncServer, "_consume", broken)


def test_answer_altered_where_produced_is_caught(monkeypatch):
    _break_consume(monkeypatch, lambda i, b, pos: pos + (i == 0))
    res = _execute(small_cell("dna_chr.seeds", n=30000))
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_half_of_each_batch_left_out_is_caught(monkeypatch):
    _break_consume(monkeypatch,
                   lambda i, b, pos: pos if i < b // 2 else pos[:0])
    res = _execute(small_cell("dna_chr.seeds", n=30000))
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("name", ["dna_chr", "swissprot"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_the_build_comparison(name, seed):
    reading = CONTROL.reading(small_cell(f"{name}.build", n=30000), seed)
    assert reading["correct"] is False
    mism = reading["checks"]["leaf_mismatches"]
    assert mism["value"] > mism["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_the_lookup_comparison(seed):
    reading = CONTROL.reading(small_cell("dna_chr.seeds", n=30000), seed)
    assert reading["correct"] is False
    wrong = reading["checks"]["wrong_answers"]
    assert wrong["value"] > wrong["limit"]

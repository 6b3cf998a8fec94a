"""``open_lookup``: an open loop of pattern lookups through
``AsyncServer``, requests offered at ``rate_per_s`` with Poisson arrivals
whatever the server does.

Each request is timed from when it was due, so a stall counts against the
requests behind it too; a request the full admission queue refuses counts
in the run's ``failed``.  Set-up, check and control are
``harness/lookup.py``'s.  ``sweep.py`` drives this loop at a range of rates
to find the highest the server sustains.
"""

from __future__ import annotations

import time

import numpy as np

from harness import data, lookup
from harness.runs import GcPauses, Run, obs_begin, obs_end, peak_bytes

check = lookup.check
control = lookup.control


def open_loop(server, pool, rate: float, seconds: float,
              rng: np.random.Generator, *, keep,
              t_begin: float | None = None) -> dict:
    """Offer requests at ``rate`` per second, Poisson arrivals, for
    ``seconds``.  Returns the latencies of the requests answered in time,
    the pending ones, the kept answers ``{rid: positions}``, the answer
    with the most positions, the submitted and refused counts, and how
    late the generator ran (mean and most, seconds)."""
    from jax.profiler import TraceAnnotation

    t0 = time.perf_counter() if t_begin is None else t_begin
    t_end = t0 + seconds
    due = t0 + np.cumsum(rng.exponential(1.0 / rate,
                                         int(rate * seconds * 1.1) + 64))
    rid0 = lookup.next_rid(server)
    pending: dict[int, float] = {}
    latencies: list[float] = []
    kept: dict = {}
    largest = [-1, None]
    late_sum = late_max = 0.0
    i, rejected, size = 0, 0, len(pool)
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        with TraceAnnotation("bench/submit"):
            while i < len(due) and due[i] <= now:
                t_due = float(due[i])
                if server.submit(rid0 + i, pool[i % size], now=t_due):
                    pending[rid0 + i] = t_due
                else:
                    rejected += 1
                late_sum += now - t_due
                late_max = max(late_max, now - t_due)
                i += 1
        with TraceAnnotation("bench/pump"):
            did = server.pump()
        done = time.perf_counter()
        if done >= t_end:
            break
        lookup.take_results(server, pending, done, latencies, keep, size,
                            rid0, kept, largest)
        if not did and i < len(due) and due[i] - done > 100e-6:
            time.sleep(50e-6)
    return {"latencies": latencies, "kept": kept, "largest": largest,
            "pending": dict(pending), "submitted": i, "rejected": rejected,
            "rid0": rid0, "late_s": (late_sum / max(1, i), late_max)}


def drive(run: Run, seconds: float, hooks) -> None:
    rate = float(run.traffic["rate_per_s"])
    warm_rng = data.rng_for(~run.seed, data.TRAFFIC)
    server, pool = lookup.setup(
        run, lambda srv, reqs, secs: open_loop(srv, reqs, rate, secs,
                                               warm_rng, keep=None))
    keep = lookup.keep_mask(run, len(pool))
    arrivals = data.rng_for(run.seed, data.ARRIVALS)
    gc_pauses = GcPauses()
    gc_pauses.settle()
    before = obs_begin(hooks)
    compiles0 = run.compiles_setup = hooks.compile_count()
    with hooks.window_ctx():
        t0 = time.perf_counter()
        run.setup_s = t0 - hooks.t_start
        loop = open_loop(server, pool, rate, seconds, arrivals, keep=keep,
                         t_begin=t0)
        run.window_s = time.perf_counter() - t0
    gc_pauses.done(run)
    run.compiles_in_window = hooks.compile_count() - compiles0
    lookup.finish(run, server, pool, loop)
    obs_end(hooks, run, before)
    run.memory_peak_bytes = peak_bytes()
    mean, most = loop["late_s"]
    print(f"generator late by {mean!r} s on average, {most!r} s at most; "
          f"{loop['rejected']} requests rejected", flush=True)

"""``closed_lookup``: a closed loop of pattern lookups through
``AsyncServer``, ``outstanding`` requests in flight at all times.

As each answer comes back another request is submitted, so the server
always has a full batch ready while another runs: the loop measures the
rate the server sustains, and each request is timed from its submission
to its answer's receipt by the loop.  Set-up, check and control are
``harness/lookup.py``'s.
"""

from __future__ import annotations

import time

from harness import lookup
from harness.runs import GcPauses, Run, obs_begin, obs_end, peak_bytes

check = lookup.check
control = lookup.control


def closed_loop(server, pool, outstanding: int, seconds: float, *, keep,
                t_begin: float | None = None) -> dict:
    """Keep ``outstanding`` requests in the server for ``seconds``.
    Returns the latencies of the requests answered in time, the pending
    ones, the kept answers ``{rid: positions}``, the answer with the most
    positions, and the submitted and refused counts."""
    from jax.profiler import TraceAnnotation

    t0 = time.perf_counter() if t_begin is None else t_begin
    t_end = t0 + seconds
    rid0 = lookup.next_rid(server)
    pending: dict[int, float] = {}
    latencies: list[float] = []
    kept: dict = {}
    largest = [-1, None]
    i, rejected, size = 0, 0, len(pool)
    while True:
        with TraceAnnotation("bench/submit"):
            now = time.perf_counter()
            while len(pending) < outstanding:
                i += 1
                if not server.submit(rid0 + i - 1, pool[(i - 1) % size],
                                     now=now):
                    rejected += 1
                    break
                pending[rid0 + i - 1] = now
        with TraceAnnotation("bench/pump"):
            server.pump()
        done = time.perf_counter()
        if done >= t_end:
            break
        lookup.take_results(server, pending, done, latencies, keep, size,
                            rid0, kept, largest)
    return {"latencies": latencies, "kept": kept, "largest": largest,
            "pending": dict(pending), "submitted": i, "rejected": rejected,
            "rid0": rid0}


def drive(run: Run, seconds: float, hooks) -> None:
    outstanding = int(run.traffic["outstanding"])
    server, pool = lookup.setup(
        run, lambda srv, reqs, secs: closed_loop(srv, reqs, outstanding,
                                                 secs, keep=None))
    keep = lookup.keep_mask(run, len(pool))
    gc_pauses = GcPauses()
    gc_pauses.settle()
    before = obs_begin(hooks)
    compiles0 = run.compiles_setup = hooks.compile_count()
    with hooks.window_ctx():
        t0 = time.perf_counter()
        run.setup_s = t0 - hooks.t_start
        loop = closed_loop(server, pool, outstanding, seconds, keep=keep,
                           t_begin=t0)
        run.window_s = time.perf_counter() - t0
    gc_pauses.done(run)
    run.compiles_in_window = hooks.compile_count() - compiles0
    lookup.finish(run, server, pool, loop)
    obs_end(hooks, run, before)
    run.memory_peak_bytes = peak_bytes()

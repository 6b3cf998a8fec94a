"""What the lookup loops share: the index, the server and the request
streams of set-up, the answers kept for the check, the check, and the
control.

Set-up builds a ``DeviceIndex`` of the run's text, makes the request pool
from the seed, serves batches of every shape the window can dispatch,
and runs the loop itself for ``warmup_s`` on requests of another stream.
Check: a sample drawn from the seed among the window's first requests,
those of them completed in the window, and the answer with the most
positions, equal the brute-force occurrences; and every request admitted
in the window is answered, waiting past the close for those still due.
"""

from __future__ import annotations

import numpy as np

from harness import data, reference
from harness.runs import Check, Run, era_config, program_alphabet


def _serve_config(traffic: dict):
    from repro.launch.serving import ServeConfig
    return ServeConfig(**traffic["serve"])


def _warm_shapes(server, warm: list) -> None:
    """Serve batches of every power-of-two size up to ``max_batch``, so
    every (width, rows) shape the window can dispatch is compiled."""
    i, b = 0, 1
    while b <= server.config.max_batch:
        server.serve(warm[i:i + b])
        i += b
        b *= 2


def setup(run: Run, warm_loop):
    """The index, the server and the request pool of a lookup run, with
    every shape the window can dispatch warmed and ``warm_loop(server,
    requests, seconds)`` run for ``warmup_s``.  Returns (server, pool)."""
    import jax
    from repro.core.api import EraIndexer
    from repro.launch.serving import AsyncServer

    tr = run.traffic
    k = run.alphabet_size
    run.text = data.make_text(run.config, run.seed)
    dev = EraIndexer(program_alphabet(run.config),
                     era_config(run.config)).build_device(run.text)
    jax.block_until_ready(dev.ell)
    run.n_leaves = dev.n_leaves
    server = AsyncServer(dev, _serve_config(tr))
    pool = data.make_seeds(run.text, tr, run.seed, int(tr["pool"]), k)
    # warm-up patterns come from another stream, so the window's requests
    # meet a route cache that has seen none of them
    warm = data.make_seeds(run.text, tr, ~run.seed, int(tr["warm_pool"]), k)
    _warm_shapes(server, warm)
    # a short run of the same loop: the host paths settle
    warm_loop(server, warm, float(tr["warmup_s"]))
    server.drain()
    server.results.clear()
    return server, pool


def keep_mask(run: Run, size: int) -> np.ndarray:
    """Which requests' answers the check compares: a draw from the seed
    among the window's first ``check_from_first``."""
    tr = run.traffic
    keep = np.zeros(size, bool)
    keep[data.rng_for(run.seed, data.SAMPLE).choice(
        int(tr["check_from_first"]), int(tr["check_sample"]),
        replace=False)] = True
    return keep


def next_rid(server) -> int:
    return server.n_admitted + server.n_rejected


def finish(run: Run, server, pool, loop: dict) -> None:
    """After the window: wait for the answers still due (late is late,
    not wrong), then fill ``run`` from the loop's record."""
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("bench/drain"):
        server.drain()
    never = [rid for rid in loop["pending"] if rid not in server.results]
    run.latencies_s = loop["latencies"]
    run.attempted = loop["submitted"]
    run.failed = len(never) + loop["rejected"]
    run.lookup = {"kept": loop["kept"], "largest": loop["largest"],
                  "pool": pool, "rid0": loop["rid0"], "never": len(never),
                  "rejected": loop["rejected"]}


def take_results(server, pending: dict, done: float, latencies: list,
                 keep, size: int, rid0: int, kept: dict, largest: list) -> None:
    """Move every answer the server holds into the loop's record: its
    latency from ``pending``'s start time to ``done``, the answer itself
    where ``keep`` marks it, and the answer with the most positions."""
    results = server.results
    for rid in list(results):
        pos = results.pop(rid)[0]
        latencies.append(done - pending.pop(rid))
        j = rid - rid0
        if keep is not None and j < size and keep[j]:
            kept[rid] = pos
        if largest[1] is None or len(pos) > len(largest[1]):
            largest[0], largest[1] = rid, pos


def check(run: Run) -> None:
    """A seeded sample of the answers completed in the window, and the one
    with the most positions, against brute-force occurrences."""
    lk = run.lookup
    text_bytes = run.text.tobytes()
    answers = dict(lk["kept"])
    if lk["largest"][1] is not None:
        answers[lk["largest"][0]] = lk["largest"][1]
    wrong = 0
    for rid, pos in answers.items():
        pat = lk["pool"][(rid - lk["rid0"]) % len(lk["pool"])]
        if reference.occurrences(text_bytes, pat) != [int(p) for p in pos]:
            wrong += 1
    limits = run.traffic["limits"]
    run.checks.append(Check("wrong_answers", wrong, limits["wrong_answers"]))
    run.checks.append(Check("never_answered", lk["never"],
                            limits["never_answered"]))
    run.checks.append(Check("answers_checked", len(answers),
                            limits["answers_checked"], at_least=True))
    run.failed += wrong


def control(run: Run) -> None:
    """The control's answers to the requests the check samples: patterns
    matched on their first ``w_max`` symbols only."""
    tr = run.traffic
    depth = int(run.config["era"]["w_max"])
    run.text = data.make_text(run.config, run.seed)
    pool = data.make_seeds(run.text, tr, run.seed, int(tr["pool"]),
                           run.alphabet_size)
    keep = keep_mask(run, len(pool))
    text_bytes = run.text.tobytes()
    kept = {int(i): np.asarray(reference.occurrences(text_bytes, pool[i],
                                                     depth=depth))
            for i in np.flatnonzero(keep)}
    run.lookup = {"kept": kept, "largest": [None, None], "pool": pool,
                  "rid0": 0, "never": 0, "rejected": 0}
    run.attempted = len(kept)

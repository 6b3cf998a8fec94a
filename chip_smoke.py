#!/usr/bin/env python3
"""Build and serve a chromosome-scale ERA index on a TPU, and check it.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded fabric on four chips

One chip: 2**25 symbols of synthetic repeat-rich DNA, dense-packed at 2
bits, go through the normal path — ``EraIndexer.build_device`` (vertical
partition into at least 8 virtual trees, the elastic-range prepare loop,
flatten) → ``DeviceIndex`` → ``AsyncServer`` — and 4 batches of 256
patterns (8-32 symbols, 70% planted) are served.  Checks: the leaf array
is a permutation of every suffix, sampled adjacent leaves are in suffix
order, every planted pattern hits its position, sampled hit sets equal
brute-force occurrences, and every kernel dispatch ran compiled Pallas.

Four chips (``--chips 4``): the same string through
``EraIndexer.build_sharded`` (``sharded_prepare`` over a 4-device mesh +
``ShardedIndex``) and a sharded ``AsyncServer``, compared with a
one-device ``build_device``: equal leaf arrays, equal hits, and the four
shards on four distinct devices.

Stage times printed here are smoke wall times (cold, compiles included),
not benchmark numbers.  The script exits non-zero, printing no ok line,
unless JAX's first device is a TPU; its last line on success is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N = 1 << 25          # text symbols
MIN_GROUPS = 8       # virtual trees the vertical partition must yield
BATCHES, BATCH = 4, 256
PLANTED = 0.7
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"check FAILED: {what}")
    log(f"check ok: {what}")


class Stage:
    """Print one stage's smoke wall time."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            log(f"smoke time {self.name}: "
                f"{time.perf_counter() - self.t0:.3f}s")


def era_config(n: int):
    """Dense packing (the default) and a memory budget whose f_max splits
    the n + 1 suffixes into at least MIN_GROUPS virtual trees — the shape
    a whole genome takes against one chip's HBM.

    The read range is held at 16 symbols (a 16-symbol-per-suffix read
    buffer, range cap 16) and tail compaction is off, so the elastic loop
    compiles one step program: each new (range, compacted width) pair is
    a fresh compile of the fused sort, about a minute apiece for the TPU
    on a CPU host, and the cold run has to stay well inside its time
    limit.  Results do not depend on either setting."""
    from repro.core.api import NODE_BYTES, EraConfig

    f_max = (n + 1) // (MIN_GROUPS + 1)
    return EraConfig(memory_bytes=-(-f_max * 2 * NODE_BYTES * 5 // 3),
                     r_bytes=16 * (n + 1), w_max=16, compaction=False,
                     build_impl="none")


def make_patterns(s, rng):
    """BATCHES x BATCH patterns of 8-32 symbols; PLANTED of them copied
    from the text.  Returns (patterns, planted positions or -1)."""
    n = len(s) - 1
    pats, where = [], []
    for _ in range(BATCHES * BATCH):
        m = int(rng.integers(8, 33))
        if rng.random() < PLANTED:
            p = int(rng.integers(0, n - m))
            pats.append(np.asarray(s[p:p + m], np.int32))
            where.append(p)
        else:
            pats.append(rng.integers(0, 4, m).astype(np.int32))
            where.append(-1)
    return pats, where


def occurrences(text: bytes, pat) -> list[int]:
    """Brute-force start positions of ``pat`` in ``text`` (overlaps too)."""
    needle = bytes(int(c) for c in pat)
    out, i = [], text.find(needle)
    while i >= 0:
        out.append(i)
        i = text.find(needle, i + 1)
    return out


def suffix_less(s, a: int, b: int) -> bool:
    """S[a:] < S[b:] on the host (the terminal is the unique largest
    code, so the first difference always decides)."""
    k = 0
    while True:
        x, y = s[a + k:a + k + 4096], s[b + k:b + k + 4096]
        m = min(len(x), len(y))
        d = np.flatnonzero(x[:m] != y[:m])
        if d.size:
            return bool(x[d[0]] < y[d[0]])
        if m < 4096:
            return len(x) < len(y)
        k += 4096


def serve(server, pats, name: str):
    results = []
    for b in range(BATCHES):
        with Stage(f"{name} batch {b} ({BATCH} patterns)"):
            results += server.serve(pats[b * BATCH:(b + 1) * BATCH])
    return [np.asarray(r[0]) for r in results]


def check_leaves(s, ell, rng) -> None:
    n1 = len(s)
    check(ell.shape == (n1,) and np.array_equal(np.sort(ell), np.arange(n1)),
          f"leaf array is a permutation of [0, {n1 - 1}]")
    idx = rng.integers(0, n1 - 1, 4096)
    check(all(suffix_less(s, int(ell[i]), int(ell[i + 1])) for i in idx),
          f"{idx.size} sampled adjacent leaves are in suffix order")


def check_hits(s, pats, where, hits, rng) -> None:
    planted = [i for i, p in enumerate(where) if p >= 0]
    check(all(where[i] in set(hits[i].tolist()) for i in planted),
          f"all {len(planted)} planted patterns hit their position")
    text = s.astype(np.uint8).tobytes()
    sample = rng.choice(len(pats), min(64, len(pats)), replace=False)
    check(all(occurrences(text, pats[i]) == hits[i].tolist() for i in sample),
          f"{sample.size} sampled hit sets equal the brute-force occurrences")


def check_dispatch() -> None:
    from repro import obs

    counts: dict[str, float] = {}
    impls: set[str] = set()
    for c in obs.metrics().snapshot()["counters"]:
        if c["name"] == "kernel_traces_total":
            labels = c["labels"]
            key = f"{labels['kernel']}/{labels['currency']}/{labels['impl']}"
            counts[key] = counts.get(key, 0) + c["value"]
            impls.add(labels["impl"])
    log(f"kernel traces: {json.dumps(counts, sort_keys=True)}")
    check(impls == {"pallas"},
          "every kernel traced is compiled Pallas (0 ref, 0 interpreted)")


def one_chip(s, alphabet, rng) -> None:
    from repro.core.api import BuildReport, EraIndexer
    from repro.core.prepare import PrepareStats
    from repro.core.vertical import VerticalStats
    from repro.launch.serving import AsyncServer

    cfg = era_config(len(s) - 1)
    report = BuildReport(VerticalStats(), PrepareStats())
    with Stage("build_device (partition + prepare + flatten)"):
        dev = EraIndexer(alphabet, cfg).build_device(s, report)
        jax.block_until_ready(dev.ell)
    log(f"prefixes: {report.n_prefixes}, virtual trees: {report.n_groups}, "
        f"f_max: {report.f_max}, memory_bytes: {cfg.memory_bytes}")
    log(f"elastic iterations: {report.prepare.iterations}, "
        f"ranges: {report.prepare.ranges}, "
        f"active: {report.prepare.active_history}")
    log(f"smoke time vertical partition: {report.t_vertical:.3f}s, "
        f"prepare loop: {report.t_prepare:.3f}s")
    check(report.n_groups >= MIN_GROUPS,
          f"{report.n_groups} virtual trees (>= {MIN_GROUPS})")
    check_leaves(s, dev.ell_host, rng)

    pats, where = make_patterns(s, rng)
    hits = serve(AsyncServer(dev), pats, "serve")
    check_hits(s, pats, where, hits, rng)


def four_chips(s, alphabet, rng) -> None:
    from repro.core.api import EraIndexer
    from repro.launch.serving import AsyncServer

    cfg = era_config(len(s) - 1)
    indexer = EraIndexer(alphabet, cfg)
    with Stage("one-device build_device"):
        dev = indexer.build_device(s)
        jax.block_until_ready(dev.ell)
    with Stage("sharded build (4 devices)"):
        sh = indexer.build_sharded(s)
        jax.block_until_ready([d.ell for d in sh.shards])
    places = [next(iter(d.ell.devices())) for d in sh.shards]
    log(f"shard devices: {[str(p) for p in places]}")
    check(len(sh.shards) == 4 and len(set(places)) == 4,
          "the 4 shards sit on 4 distinct devices")
    flat = np.concatenate([d.ell_host for d in sh.shards])
    check(np.array_equal(flat, dev.ell_host),
          "the sharded leaf array equals the one-device build")

    pats, _ = make_patterns(s, rng)
    one = serve(AsyncServer(dev), pats, "one-device serve")
    many = serve(AsyncServer(sh), pats, "sharded serve")
    check(all(np.array_equal(a, b) for a, b in zip(one, many)),
          f"sharded hits equal the one-device hits for {len(pats)} patterns")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: build and serve on one chip; 4: the sharded "
                         "fabric against a one-device build")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{device.platform}; nothing was run", file=sys.stderr)
        return 1
    count = len(jax.devices())
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {count}", file=sys.stderr)
        return 1

    from repro import obs
    from repro.data.strings import dataset
    from repro.launch.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    log(f"device: {device.device_kind} x{count}")
    obs.configure(metrics_on=True)
    rng = np.random.default_rng(SEED + 1)
    t0 = time.perf_counter()
    with Stage("data"):
        s, alphabet = dataset("genome", N, seed=SEED)
    log(f"n: {len(s) - 1} symbols ({alphabet.name}, "
        f"{alphabet.dense_bits}-bit dense)")
    if args.chips == 1:
        one_chip(s, alphabet, rng)
    else:
        four_chips(s, alphabet, rng)
    check_dispatch()
    stats = device.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    log(f"smoke time total: {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Sustained batched analytics serving driver (read-side, like query_serve).

Builds an ERA index over a dataset, lifts it into the device-resident
:class:`repro.core.analytics.AnalyticsEngine`, then drives a sustained loop
of matching-statistics batches (the analytics workload with a per-request
shape: one query string in, per-position longest-match lengths + witnesses
out) and reports positions/sec plus per-batch latency.  Repeat mining and
k-mer spectra are one-shot index-wide passes, so they are reported once at
startup rather than looped.

CPU example:
  PYTHONPATH=src python -m repro.launch.analytics_serve --dataset dna \
      --n 100000 --batch 512 --iters 20 --index-path /tmp/era_analytics.npz
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.analytics import AnalyticsEngine
from repro.core.api import EraConfig, EraIndexer
from repro.launch.warmstart import load_or_build
from repro.launch.compile_cache import use_compile_cache


def make_query(s: np.ndarray, rng: np.random.Generator, *, batch: int,
               planted_frac: float, n_symbols: int) -> np.ndarray:
    """A query string of ``batch`` positions: planted slices of S (long
    matches) spliced with random stretches (short matches)."""
    out = np.empty(batch, np.uint8)
    i = 0
    while i < batch:
        m = int(rng.integers(8, 65))
        m = min(m, batch - i)
        if rng.random() < planted_frac:
            j = int(rng.integers(0, len(s) - 1 - m))
            out[i : i + m] = s[j : j + m]
        else:
            out[i : i + m] = rng.integers(0, n_symbols, size=m)
        i += m
    return out


def serve_analytics(dataset_name: str = "dna", *, n: int = 100_000,
                    batch: int = 512, iters: int = 20, window: int = 64,
                    planted_frac: float = 0.7, memory_bytes: int = 1 << 20,
                    seed: int = 0, index_path: str | None = None):
    if iters < 1 or batch < 1:
        raise ValueError(f"need iters >= 1 and batch >= 1, got {iters}, {batch}")
    rng = np.random.default_rng(seed + 1)

    def build(s, alphabet):
        cfg = EraConfig(memory_bytes=memory_bytes, build_impl="none")
        return EraIndexer(alphabet, cfg).build_analytics(s)[1]

    # warm start: one npz holds the flattened index AND the LCP array
    eng, s, alphabet, t_build = load_or_build(
        index_path, dataset_name, n, seed,
        load=AnalyticsEngine.load, build=build, dev_of=lambda e: e.dev)
    if len(s) <= 66:  # make_query plants slices up to 64 symbols
        raise ValueError(f"indexed string too short ({len(s)} symbols)")

    # index-wide one-shot passes (reported once, not looped)
    rep = eng.longest_repeat()
    distinct = eng.distinct_substrings()

    queries = [make_query(s, rng, batch=batch, planted_frac=planted_frac,
                          n_symbols=len(alphabet.symbols))
               for _ in range(iters)]
    ms, wit = eng.matching_stats(queries[0], window=window)  # warmup/compile

    lat = []
    matched = 0
    t0 = time.perf_counter()
    for q in queries:
        t1 = time.perf_counter()
        ms, wit = eng.matching_stats(q, window=window)
        lat.append(time.perf_counter() - t1)
        matched += int(ms.sum())
    t_serve = time.perf_counter() - t0

    lat = np.array(lat)
    return {
        "dataset": dataset_name,
        "n_symbols": eng.total,
        "n_subtrees": eng.dev.n_subtrees,
        "t_build_s": round(t_build, 3),
        "longest_repeat": None if rep is None else rep["length"],
        "distinct_substrings": distinct,
        "batches": iters,
        "batch": batch,
        "positions": iters * batch,
        "mean_match_len": round(matched / (iters * batch), 2),
        "positions_per_s": round(iters * batch / max(t_serve, 1e-9), 1),
        "batch_p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
        "batch_p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
    }


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="dna")
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--batch", type=int, default=512,
                    help="query positions per batch (the query length)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--window", type=int, default=64,
                    help="matching-statistics length cap")
    ap.add_argument("--planted-frac", type=float, default=0.7)
    ap.add_argument("--index-path", default=None,
                    help="npz cache: load index+LCP if the file exists, "
                         "else build once and save there")
    args = ap.parse_args()
    stats = serve_analytics(args.dataset, n=args.n, batch=args.batch,
                            iters=args.iters, window=args.window,
                            planted_frac=args.planted_frac,
                            index_path=args.index_path)
    print(stats)


if __name__ == "__main__":
    main()

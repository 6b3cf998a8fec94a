#!/usr/bin/env python3
"""Find the highest rate a lookup cell's server sustains: one set-up, then
the cell's open loop at each rate in turn.

    python3 benchmarks/chip/sweep.py --workload dna_chr.seeds --seed 5 \
        --seconds 5 --rates 10000,20000,30000

It drives ``loops/open_lookup.py`` whatever loop the cell's own traffic
names, so a closed-loop cell's set-up serves for the sweep too.  For each
rate it prints the offered and completed rates, the latency
percentiles, how many requests were still pending at the close, and how
late the generator ran.  A rate is sustained where the completed rate
matches the offered one and the pending count stays near a batch.  The
cell's ``rate_per_s`` is set from such a sweep, once, and fixed; the
benchmark's runs never sweep.  Needs a TPU, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
# libtpu would otherwise write its logs to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

from harness import data, lookup, spec  # noqa: E402
from harness.runs import Run, use_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve_cell(spec.load_benchmark(ROOT), args.workload)
    if "pool" not in cell.traffic:
        print(f"sweep: {cell.name} is not a lookup cell", file=sys.stderr)
        return 2
    open_loop = spec.load_loop("open_lookup").open_loop
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU; nothing was measured", file=sys.stderr)
        return 2
    use_cache()
    run = Run(cell=cell.name, config=cell.config, traffic=cell.traffic,
              seed=args.seed, n=int(cell.config["n"]))
    rng = data.rng_for(args.seed, data.ARRIVALS)
    server, pool = lookup.setup(
        run, lambda srv, reqs, secs: open_loop(srv, reqs, 1000.0, secs, rng,
                                               keep=None))
    for rate in (float(r) for r in args.rates.split(",")):
        out = open_loop(server, pool, rate, args.seconds, rng, keep=None)
        server.drain()
        server.results.clear()
        lat = np.asarray(out["latencies"]) * 1e3
        pct = (np.percentile(lat, [50, 95, 99]).tolist() if lat.size
               else [None] * 3)
        print(json.dumps({
            "rate": rate, "completed_per_s": lat.size / args.seconds,
            "p50_ms": pct[0], "p95_ms": pct[1], "p99_ms": pct[2],
            "pending_at_close": len(out["pending"]),
            "rejected": out["rejected"], "late_s": out["late_s"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

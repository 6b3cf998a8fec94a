# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper table/figure:

  fig7   bench_horizontal   ERA-str vs ERA-str+mem
  fig8   bench_rtuning      |R| read-buffer tuning (DNA vs protein)
  fig9a  bench_vertical     virtual trees on/off
  fig9b  bench_elastic      elastic vs static range
  fig10  bench_baselines    ERA vs WaveFront-style vs SA-based (B²ST-style)
  fig11  bench_alphabet     alphabet sensitivity
  tbl3   bench_scaling      strong/weak scaling (scheduler busy-time model)
  roofl  bench_roofline     dry-run roofline table (reads experiments/dryrun.json)
  build      bench_build      batched (G,F) construction engine vs serial loop
  query      bench_query      batched device query engine vs per-pattern Python
  analytics  bench_analytics  LCP analytics engine vs per-position Python
  packed     bench_packed     dense k-bit string gather/probe vs byte path
  fabric     bench_fabric     sharded SPMD construction vs single-device
  stream     bench_stream     out-of-core streaming build + incremental append

``python -m benchmarks.run``            — quick pass over everything
``python -m benchmarks.run --full``     — paper-scale (slower) settings
``python -m benchmarks.run --smoke``    — CI mode: quick settings,
                                          intended with --json
``python -m benchmarks.run --json results.json``  — persist rows as JSON
``python -m benchmarks.run --only fig9b``

Every mode runs all selected suites and exits non-zero if any errored.
"""

from __future__ import annotations

import argparse
import inspect
import json
import platform
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke pass: quick settings")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write all emitted rows to PATH as JSON")
    ap.add_argument("--only", default=None,
                    help="run a subset of suites (comma-separated, e.g. "
                         "--only query,packed)")
    args = ap.parse_args()
    quick = not args.full or args.smoke

    from benchmarks import (
        bench_alphabet,
        bench_analytics,
        bench_baselines,
        bench_build,
        bench_elastic,
        bench_fabric,
        bench_horizontal,
        bench_packed,
        bench_query,
        bench_roofline,
        bench_rtuning,
        bench_scaling,
        bench_stream,
        bench_vertical,
        common,
    )

    suites = {
        "fig7": bench_horizontal.run,
        "fig8": bench_rtuning.run,
        "fig9a": bench_vertical.run,
        "fig9b": bench_elastic.run,
        "fig10": bench_baselines.run,
        "fig11": bench_alphabet.run,
        "tbl3": bench_scaling.run,
        "roofline": bench_roofline.run,
        "build": bench_build.run,
        "query": bench_query.run,
        "analytics": bench_analytics.run,
        "packed": bench_packed.run,
        "fabric": bench_fabric.run,
        "stream": bench_stream.run,
    }
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - set(suites)
        if unknown:
            ap.error(f"unknown suite(s) {sorted(unknown)}; "
                     f"choose from {sorted(suites)}")
    common.RESULTS.clear()  # in-process reruns must not accumulate rows
    print("name,us_per_call,derived")
    errors: list[str] = []
    for key, fn in suites.items():
        if only and key not in only:
            continue
        try:
            if "quick" in inspect.signature(fn).parameters:
                fn(quick=quick)
            else:
                fn()
        except Exception as e:  # report, keep the suite going
            errors.append(f"{key}: {type(e).__name__}: {e}")
            print(f"{key}/ERROR,0,{type(e).__name__}: {e}", file=sys.stderr)

    if args.json:
        payload = {
            "mode": "smoke" if args.smoke else ("full" if args.full else "quick"),
            "python": platform.python_version(),
            "platform": platform.platform(),
            "provenance": common.provenance(),
            "rows": common.RESULTS,
            "errors": errors,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {len(common.RESULTS)} rows to {args.json}", file=sys.stderr)

    if errors:
        sys.exit(1)


if __name__ == "__main__":
    main()

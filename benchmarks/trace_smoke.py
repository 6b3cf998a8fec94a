"""CI trace-smoke: prove the flight recorder works AND costs ~nothing.

Runs a small build + closed-loop serve twice over the same workload with
a warm jit cache — once with tracing/metrics ON, once OFF — then:

1. exports the Chrome trace and validates it against the ``trace_event``
   schema subset (:func:`repro.obs.validate_chrome_trace`);
2. asserts the per-batch serving spans are present (``serve/queue_wait``,
   ``serve/route``, ``serve/pad_pack``, ``serve/device_dispatch``,
   ``serve/consume_sync``, ``serve/scatter``) plus the construction spans
   of a streaming and a one-shot build;
3. asserts the Prometheus snapshot carries the cache hit rate, the
   batch-fill histogram, and per-impl kernel trace counters;
4. gates overhead: instrumented qps must stay within ``--threshold`` of
   the uninstrumented run (default 0.5 — CI runners are noisy; the guard
   is against pathological slowdowns, not 5% drift).

Exit status is nonzero on any failure, with every problem printed.

    REPRO_TRACE=1 REPRO_METRICS=1 python -m benchmarks.trace_smoke \
        --out-dir /tmp/trace_smoke
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REQUIRED_SPANS = (
    "build/vertical",
    "build/flatten",
    "prepare/step",
    "prepare/readback",
    "stream/pipeline",
    "stream/chunk",
    "serve/queue_wait",
    "serve/route",
    "serve/pad_pack",
    "serve/device_dispatch",
    "serve/consume_sync",
    "serve/scatter",
)
REQUIRED_PROM = (
    "serve_cache_hit_rate",
    "serve_batch_fill_bucket",
    "serve_queue_wait_ms_bucket",
    "serve_batch_age_ms_bucket",
    "kernel_traces_total",
    "prepare_group_iterations_bucket",
)


def _serve_once(dev, pats, cfg_kw) -> float:
    """One closed-loop pass; returns qps."""
    from repro.launch.serving import AsyncServer, ServeConfig

    server = AsyncServer(dev, ServeConfig(**cfg_kw))
    t0 = time.perf_counter()
    server.serve(pats)
    return len(pats) / max(time.perf_counter() - t0, 1e-9)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=20_000,
                    help="text length for the smoke build")
    ap.add_argument("--requests", type=int, default=1024)
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed passes per arm; best-of wins (noise guard)")
    ap.add_argument("--threshold", type=float, default=0.5,
                    help="min instrumented/uninstrumented qps ratio")
    ap.add_argument("--out-dir", default=".",
                    help="where the trace/metrics artifacts land")
    args = ap.parse_args()

    from repro import obs
    from repro.core.alphabet import DNA
    from repro.core.api import EraConfig, EraIndexer
    from repro.launch.serving import make_hot_workload

    problems: list[str] = []
    cfg_kw = dict(pipeline=True, cache_size=512, max_batch=64)

    # ---- instrumented arm: build + serve with the recorder on -------------
    obs.configure(trace=True, metrics_on=True, clear=True)
    s = DNA.random_string(args.n, seed=0)
    # the streaming builder (budget forces several chunks) exercises the
    # stream/* spans and emits the same index the one-shot path would
    indexer = EraIndexer(DNA, EraConfig(
        memory_bytes=1 << 20, build_impl="none"))
    dev, sreport = indexer.build_stream(
        s, device_budget=64 << 10, max_pattern_len=64)
    print(f"stream build: {sreport.n_chunks} chunks, "
          f"overlap_frac={sreport.overlap_frac:.2f}")
    # the one-shot build adds build/flatten; both give the same leaves
    one_shot = indexer.build_device(s, max_pattern_len=64)
    if not np.array_equal(one_shot.ell_host, dev.ell_host):
        problems.append("stream and one-shot builds differ")
    rng = np.random.default_rng(7)
    pats = make_hot_workload(s, rng, n_requests=args.requests, hot_pool=32,
                             hot_frac=0.8, min_len=4, max_len=24,
                             n_symbols=4)
    _serve_once(dev, pats, cfg_kw)  # warmup: compiles + kernel counters
    qps_on = max(_serve_once(dev, pats, cfg_kw)
                 for _ in range(args.repeats))

    # ---- export + validate ------------------------------------------------
    os.makedirs(args.out_dir, exist_ok=True)
    trace_path = os.path.join(args.out_dir, "era_trace.json")
    prom_path = os.path.join(args.out_dir, "era_metrics.prom")
    obs.export_all(trace_path=trace_path, metrics_path=prom_path)
    print(f"wrote {trace_path}")
    print(f"wrote {prom_path}")

    with open(trace_path) as f:
        trace = json.load(f)
    for err in obs.validate_chrome_trace(trace):
        problems.append(f"trace schema: {err}")
    names = {e["name"] for e in trace["traceEvents"]}
    for span in REQUIRED_SPANS:
        if span not in names:
            problems.append(f"trace missing required span {span!r}")

    # span links: every serving batch's device_dispatch span must carry a
    # link id that some serve/queue_wait span also carries — the join key
    # that attributes device work back to the admission wait that fed it
    link_of = lambda e: (e.get("args") or {}).get("link")
    qw_links = {link_of(e) for e in trace["traceEvents"]
                if e["name"] == "serve/queue_wait"} - {None}
    dd_links = [link_of(e) for e in trace["traceEvents"]
                if e["name"] == "serve/device_dispatch"]
    if not dd_links or None in dd_links:
        problems.append("device_dispatch spans missing link attribute")
    elif not set(dd_links) <= qw_links:
        problems.append(
            f"device_dispatch links {sorted(set(dd_links) - qw_links)} "
            "have no matching serve/queue_wait span")
    elif not qw_links:
        problems.append("no linked serve/queue_wait spans in trace")

    with open(prom_path) as f:
        prom = f.read()
    for needle in REQUIRED_PROM:
        if needle not in prom:
            problems.append(f"prometheus snapshot missing {needle!r}")
    if 'impl="pallas"' not in prom and 'impl="ref"' not in prom:
        problems.append("kernel trace counters carry no impl label")

    # ---- uninstrumented arm: same warm jit cache, recorder off ------------
    obs.configure(trace=False, metrics_on=False)
    _serve_once(dev, pats, cfg_kw)  # warmup parity
    qps_off = max(_serve_once(dev, pats, cfg_kw)
                  for _ in range(args.repeats))

    ratio = qps_on / max(qps_off, 1e-9)
    print(f"qps instrumented={qps_on:.0f} off={qps_off:.0f} "
          f"ratio={ratio:.2f} (threshold {args.threshold})")
    if ratio < args.threshold:
        problems.append(
            f"instrumentation overhead: qps ratio {ratio:.2f} "
            f"< {args.threshold}")

    n_spans = len([e for e in trace["traceEvents"] if e.get("ph") == "X"])
    print(f"trace: {n_spans} spans, {len(names)} distinct names")
    if problems:
        for p in problems:
            print(f"FAIL: {p}", file=sys.stderr)
        sys.exit(1)
    print("trace_smoke: OK")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload dna_chr.build --seed 7 \
        --seconds 10 --trace 0

The cell, its configuration, its traffic mix, the loop the mix names and
the cell's metrics are found by name from ``BENCHMARK.json`` at the root
of the checkout (see ``harness/spec.py``).  The run refuses, printing no result, unless JAX's
first device is a TPU and there are as many as the cell asks for.  Set-up
(data from the seed, the index or the warm-up builds, every shape the
window uses) is timed as ``setup_s``; the window then runs for
``--seconds``; nothing may compile inside it, and the count is printed.
Once the window has closed, the loop compares what the window produced
with the plain reference (``harness/reference.py``), each number beside
its limit.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the window with the JAX profiler and reports its per-layer metrics, the
device's busy time and a breakdown.  The last line of standard output is
one JSON object; the last lines of standard error are the checks.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
# libtpu would otherwise write its logs to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))

from harness import spec  # noqa: E402
from harness.runs import Hooks, Run, use_cache  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(msg, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class CompileCounter:
    """Counts backend compilations (loads from the persistent cache
    included) and the cache's hits, from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_hit)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def _on_hit(self, event: str, **kwargs) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __call__(self) -> int:
        return self.count


def execute(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
            t_start: float, bench_dir: Path = BENCH_DIR) -> dict:
    """Everything of a run after the look for a chip: the loop, the
    checks, the metrics.  Returns the result object."""
    import jax
    from repro import obs
    from harness import trace as trace_mod

    counter = CompileCounter()
    device = jax.devices()[0]
    loop = spec.load_loop(cell.traffic["loop"], bench_dir)
    run = Run(cell=cell.name, config=cell.config, traffic=cell.traffic,
              seed=seed, n=int(cell.config["n"]),
              device_kind=device.device_kind)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        obs.configure(trace=True, metrics_on=True)
    python_calls = bool(cell.traffic.get("trace_python_calls", False))
    window_ctx = ((lambda: trace_mod.capture(trace_dir, python_calls))
                  if trace else contextlib.nullcontext)
    try:
        loop.drive(run, seconds,
                   Hooks(t_start, counter, window_ctx, obs_on=trace))
        if trace:
            t_read = time.perf_counter()
            run.trace = trace_mod.Trace(trace_mod.extract(
                trace_mod.newest_xplane(trace_dir)))
            log(f"trace read in {time.perf_counter() - t_read!r} s")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"setup_s: {run.setup_s!r}  window_s: {run.window_s!r}")
    log(f"memory stats: {device.memory_stats()}")
    log(f"compilations in set-up: {run.compiles_setup} "
        f"({counter.seconds!r} s with the window's, "
        f"{counter.cache_hits} from the persistent cache)")
    log(f"compilations in window: {run.compiles_in_window}")
    for b in run.builds:
        log(f"build: {b['wall_s']!r} s (vertical {b['t_vertical']!r} s, "
            f"prepare {b['t_prepare']!r} s, {b['iterations']} iterations)")
    if run.gc_pauses:
        g = run.gc_pauses
        log(f"collector pauses in window: {g['count']}, "
            f"{g['total_s']!r} s in all, longest {g['max_s']!r} s")
    loop.check(run)

    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        value = spec.load_reader(m["name"], bench_dir)(run)
        if value is None:
            print(f"chipbench: {m['name']} read nothing in this run",
                  file=sys.stderr)
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev_out = {"platform": device.platform, "kind": device.device_kind,
               "count": len(jax.devices()),
               "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": all(c.ok for c in run.checks),
              "attempted": run.attempted, "failed": run.failed,
              "metrics": metrics, "device": dev_out}
    if trace:
        dev_out["busy_s"] = run.trace.busy_s
        dev_out["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit,
                                 **({"at_least": True} if c.at_least else {})}
                        for c in run.checks}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = spec.resolve_cell(spec.load_benchmark(ROOT), args.workload)
    except spec.SpecError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU, JAX's first device is "
              f"{devices[0].platform}; nothing was measured", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}; nothing was measured", file=sys.stderr)
        return 2
    log(f"device: platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)}")
    log(f"compile cache: {use_cache()}")
    result = execute(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=T_START)
    for name, c in result["checks"].items():
        bound = "at least" if c.get("at_least") else "limit"
        print(f"check {name}: {c['value']} ({bound} {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

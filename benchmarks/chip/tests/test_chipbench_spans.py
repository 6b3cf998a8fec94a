"""The readers of the program's spans: known values on hand-made records
and spans, nothing (and no error) where the program has no such span, and
the program's spans on the host plane of a profiler trace, inside the
window, as the benchmark captures it."""

import pytest

import chipbench_util  # noqa: F401  (puts the harness on sys.path)
from harness import spec
from harness.runs import Run
from harness.spans import idle_inside_s, mean_ms
from harness.trace import WINDOW, Trace, capture, extract, newest_xplane

MS = 1e6
BUILD = ("prepare_idle_s.build", "flatten_host_s.build")
QUERY = ("queue_wait_ms.query", "serve_route_ms.query",
         "serve_sync_ms.query", "serve_scatter_ms.query")


def _trace():
    """A 100-ms window; the chip busy 0-10, 20-30 and 50-90 ms; the prepare
    loop at 5-45 ms and 80-130 ms (clipped to 100)."""
    return Trace({
        "host": [[WINDOW, 0.0, 100 * MS],
                 ["prepare/batch_loop", 5 * MS, 40 * MS],
                 ["prepare/batch_loop", 80 * MS, 50 * MS],
                 ["prepare/step", 5 * MS, 10 * MS]],
        "device": {"/device:TPU:0": [["a", 0.0, 10 * MS, {}],
                                     ["b", 20 * MS, 10 * MS, {}],
                                     ["c", 50 * MS, 40 * MS, {}]],
                   "/device:TPU:1": [["d", 0.0, 100 * MS, {}]]},
    })


def _span(name, dur_ms):
    return {"name": name, "ph": "X", "ts_ns": 0, "dur_ns": int(dur_ms * MS),
            "tid": 1, "depth": 0, "args": {}}


def _run(kind, spans=(), counters=None, trace=None, builds=2):
    run = Run(cell="x", config={}, traffic={}, seed=1, n=10)
    run.spans = list(spans)
    run.counters = dict(counters or {})
    run.trace = trace
    if kind == "build":
        run.builds = [{"wall_s": 1.0}] * builds
    else:
        run.lookup = {}
    return run


def _read(name, run):
    return spec.load_reader(name)(run)


def test_idle_inside_named_events():
    # busiest chip (3 ops): idle inside 5-45 is 10-20 and 30-45 (25 ms),
    # inside 80-100 is 90-100 (10 ms); inside the step 5-15, 10-15
    assert idle_inside_s(_trace(), "prepare/batch_loop") == \
        pytest.approx(0.035)
    assert idle_inside_s(_trace(), "prepare/step") == pytest.approx(0.005)
    assert idle_inside_s(_trace(), "absent") is None
    assert idle_inside_s(None, "prepare/batch_loop") is None


def test_prepare_idle_per_build():
    run = _run("build", trace=_trace(), builds=2)
    assert _read("prepare_idle_s.build", run) == pytest.approx(0.0175)


def test_flatten_host_mean():
    spans = [_span("build/flatten", 400), _span("build/flatten", 500),
             _span("build/total", 9000)]
    assert _read("flatten_host_s.build", _run("build", spans)) == \
        pytest.approx(0.45)


@pytest.mark.parametrize("name,span", [
    ("queue_wait_ms.query", "serve/queue_wait"),
    ("serve_sync_ms.query", "serve/consume_sync"),
    ("serve_scatter_ms.query", "serve/scatter"),
    ("serve_route_ms.query", "serve/route"),
])
def test_query_span_means(name, span):
    spans = [_span(span, 2.0), _span(span, 4.0), _span("serve/pad_pack", 9)]
    run = _run("lookup", spans, {"serve_batches_total": 2.0})
    assert _read(name, run) == pytest.approx(3.0)
    assert mean_ms(run, span) == pytest.approx(3.0)


def test_route_needs_a_span_for_every_batch():
    spans = [_span("serve/route", 2.0), _span("serve/route", 4.0)]
    run = _run("lookup", spans, {"serve_batches_total": 3.0})
    assert _read("serve_route_ms.query", run) is None
    run.counters = {}
    assert _read("serve_route_ms.query", run) is None


@pytest.mark.parametrize("name", BUILD + QUERY)
def test_nothing_to_read_without_the_span(name):
    """A program without the new spans (the parent of this benchmark's
    readers) reads nothing, and no reader raises; nor in the other kind
    of cell."""
    trace = Trace({"host": [[WINDOW, 0.0, 100 * MS]],
                   "device": {"/device:TPU:0": [["a", 0.0, 10 * MS, {}]]}})
    kind = "build" if name in BUILD else "lookup"
    other = "lookup" if kind == "build" else "build"
    assert _read(name, _run(kind, trace=trace,
                            counters={"serve_batches_total": 5.0})) is None
    spans = [_span(s, 1.0) for s in ("build/flatten", "serve/queue_wait",
                                     "serve/route", "serve/consume_sync",
                                     "serve/scatter")]
    assert _read(name, _run(other, spans, trace=_trace())) is None


def test_program_span_on_the_captured_host_plane(tmp_path):
    """An enabled span of the program, under the benchmark's own capture
    on the CPU, is a host event of the same name inside the window."""
    from repro.obs import Tracer

    tr = Tracer(enabled=True)
    with capture(str(tmp_path), python_calls=False):
        with tr.span("serve/route", rows=4):
            with tr.span("serve/scatter"):
                sum(range(1000))
    host = extract(newest_xplane(str(tmp_path)))["host"]
    named = {h[0]: (h[1], h[1] + h[2]) for h in host}
    win, route, scatter = (named[n] for n in (WINDOW, "serve/route",
                                              "serve/scatter"))
    assert win[0] <= route[0] <= scatter[0] <= scatter[1] <= route[1] \
        <= win[1]


@pytest.mark.parametrize("name,expect", [
    ("dna_chr.build", ("flatten_host_s.build",)),
    ("dna_chr.seeds", QUERY),
])
def test_traced_cpu_run_reads_the_spans(name, expect):
    """A whole ``--trace 1`` run at test size on the CPU: the readers of
    host spans find the program's spans (the CPU trace has no device
    plane, so the device-idle reader reads nothing), and the run is
    correct."""
    import time

    from chipbench_util import load_run_module, small_cell

    cell = small_cell(name, n=30000 if name.endswith("seeds") else 20000)
    res = load_run_module().execute(cell, seed=2**31 + 77, seconds=0.5,
                                    trace=True, t_start=time.perf_counter())
    assert res["correct"]
    for m in expect:
        assert res["metrics"][m]["value"] > 0, m
    assert "prepare_idle_s.build" not in res["metrics"]

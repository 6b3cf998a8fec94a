"""Observability subsystem: overhead contract, exporters, correctness.

The obs layer's promises, in order of importance:

1. **Disabled mode is a no-op** — hot paths (serving loop, kernel
   dispatch) call ``span()``/``counter()`` unconditionally, so with the
   knobs off those must return shared null singletons and record nothing.
2. **Exporters round-trip** — span names/attributes survive both the
   Chrome ``trace_event`` export (and validate against the schema subset)
   and the JSONL export.
3. **Histograms are honest** — fixed-bucket percentiles land within a
   bucket's width of the numpy ground truth.
4. **Thread safety** — the async serving loop plus worker threads hammer
   one counter/histogram concurrently; totals must be exact.
"""

import json
import threading

import numpy as np
import pytest

from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    Metrics,
    NULL_INSTRUMENT,
    pow2_buckets,
)
from repro.obs.trace import NULL_SPAN, Tracer, validate_chrome_trace


# ---------------------------------------------------------------------------
# disabled-mode no-op guarantees
# ---------------------------------------------------------------------------

class TestDisabledMode:
    def test_disabled_span_is_shared_null(self):
        tr = Tracer(enabled=False)
        assert tr.span("a") is NULL_SPAN
        assert tr.span("b", rows=3) is NULL_SPAN

    def test_disabled_span_records_nothing(self):
        tr = Tracer(enabled=False)
        with tr.span("a", k=1) as sp:
            sp.set(more=2)
        tr.instant("point")
        tr.complete("c", 0, 100)
        assert tr.events() == []

    def test_disabled_registry_hands_out_null_instrument(self):
        m = Metrics(enabled=False)
        assert m.counter("c") is NULL_INSTRUMENT
        assert m.gauge("g") is NULL_INSTRUMENT
        assert m.histogram("h") is NULL_INSTRUMENT
        assert m.instruments() == []

    def test_null_instrument_absorbs_everything(self):
        n = NULL_INSTRUMENT
        n.inc()
        n.inc(5)
        n.dec()
        n.set(3)
        n.observe(1.5)
        assert n.value == 0.0 and n.count == 0 and n.sum == 0.0
        assert n.percentile(99) == 0.0

    def test_disabled_exports_are_empty(self):
        tr = Tracer(enabled=False)
        m = Metrics(enabled=False)
        chrome = tr.to_chrome()
        assert validate_chrome_trace(chrome) == []
        assert [e for e in chrome["traceEvents"] if e["ph"] != "M"] == []
        assert m.to_prometheus() == ""

    def test_env_knob_gates(self, monkeypatch):
        monkeypatch.setenv("REPRO_TRACE", "0")
        monkeypatch.setenv("REPRO_METRICS", "")
        assert Tracer().enabled is False
        assert Metrics().enabled is False
        monkeypatch.setenv("REPRO_TRACE", "1")
        monkeypatch.setenv("REPRO_METRICS", "1")
        assert Tracer().enabled is True
        assert Metrics().enabled is True


# ---------------------------------------------------------------------------
# spans: nesting, attributes, exporters
# ---------------------------------------------------------------------------

class TestTracer:
    def test_span_nesting_depths(self):
        tr = Tracer(enabled=True)
        with tr.span("outer"):
            with tr.span("middle"):
                with tr.span("inner"):
                    pass
        by_name = {e["name"]: e for e in tr.events()}
        assert by_name["outer"]["depth"] == 0
        assert by_name["middle"]["depth"] == 1
        assert by_name["inner"]["depth"] == 2

    def test_nesting_contains_child_interval(self):
        tr = Tracer(enabled=True)
        with tr.span("outer"):
            with tr.span("inner"):
                pass
        by_name = {e["name"]: e for e in tr.events()}
        o, i = by_name["outer"], by_name["inner"]
        assert o["ts_ns"] <= i["ts_ns"]
        assert i["ts_ns"] + i["dur_ns"] <= o["ts_ns"] + o["dur_ns"]

    def test_attribute_roundtrip_chrome(self):
        tr = Tracer(enabled=True)
        with tr.span("serve/pad_pack", rows=8, b_pad=16) as sp:
            sp.set(fill=np.float64(0.5), note="hi")
        chrome = tr.to_chrome()
        assert validate_chrome_trace(chrome) == []
        # the whole export must survive real json serialization
        evts = json.loads(json.dumps(chrome))["traceEvents"]
        (evt,) = [e for e in evts if e["name"] == "serve/pad_pack"]
        assert evt["ph"] == "X" and evt["cat"] == "serve"
        assert evt["args"] == {"rows": 8, "b_pad": 16, "fill": 0.5,
                               "note": "hi"}

    def test_attribute_roundtrip_jsonl(self):
        tr = Tracer(enabled=True)
        with tr.span("a", k=1):
            pass
        with tr.span("b", q=np.int32(7)):
            pass
        lines = tr.to_jsonl().strip().splitlines()
        objs = [json.loads(ln) for ln in lines]
        assert [o["name"] for o in objs] == ["a", "b"]
        assert objs[0]["args"] == {"k": 1}
        assert objs[1]["args"] == {"q": 7}  # numpy scalar degraded

    def test_complete_and_instant_events(self):
        tr = Tracer(enabled=True)
        origin = tr._t_origin
        tr.complete("serve/queue_wait", origin + 1000, 5000, rows=4)
        tr.instant("mark")
        evts = tr.events()
        assert evts[0]["ph"] == "X" and evts[0]["dur_ns"] == 5000
        assert evts[1]["ph"] == "i" and evts[1]["dur_ns"] == 0
        assert validate_chrome_trace(tr.to_chrome()) == []

    def test_ring_buffer_bounds_memory(self):
        tr = Tracer(capacity=8, enabled=True)
        for i in range(50):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.events()) <= 8
        assert tr.n_dropped >= 42
        # the newest span is always retained
        assert tr.events()[-1]["name"] == "s49"

    def test_clear(self):
        tr = Tracer(enabled=True)
        with tr.span("x"):
            pass
        tr.clear()
        assert tr.events() == [] and tr.n_dropped == 0

    def test_validator_catches_garbage(self):
        assert validate_chrome_trace([]) != []
        assert validate_chrome_trace({"traceEvents": [{"ph": "X"}]}) != []
        bad_dur = {"traceEvents": [
            {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0}]}
        assert any("dur" in e for e in validate_chrome_trace(bad_dur))


# ---------------------------------------------------------------------------
# metrics: instruments, percentiles, exporters
# ---------------------------------------------------------------------------

class TestInstruments:
    def test_counter_monotonic(self):
        m = Metrics(enabled=True)
        c = m.counter("reqs_total")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_registration_is_idempotent(self):
        m = Metrics(enabled=True)
        a = m.counter("c", impl="pallas")
        b = m.counter("c", impl="pallas")
        other = m.counter("c", impl="ref")
        assert a is b and a is not other
        a.inc()
        assert b.value == 1 and other.value == 0

    def test_kind_conflict_raises(self):
        m = Metrics(enabled=True)
        m.counter("x")
        with pytest.raises(ValueError):
            m.gauge("x")

    def test_callback_gauge_and_rebind(self):
        m = Metrics(enabled=True)
        box = {"v": 1.0}
        g = m.gauge("depth", fn=lambda: box["v"])
        box["v"] = 7.0
        assert g.value == 7.0
        # newest callback wins on re-registration (fresh server instance)
        m.gauge("depth", fn=lambda: 42.0)
        assert g.value == 42.0

    def test_callback_gauge_exception_is_nan(self):
        m = Metrics(enabled=True)

        def boom():
            raise RuntimeError("gone")

        g = m.gauge("dead", fn=boom)
        assert np.isnan(g.value)
        snap = m.snapshot()
        assert snap["gauges"][0]["value"] is None  # JSON-safe

    def test_histogram_bucket_validation(self):
        with pytest.raises(ValueError):
            Histogram("h", buckets=(2.0, 1.0))
        with pytest.raises(ValueError):
            Histogram("h", buckets=())

    @pytest.mark.parametrize("q", [10, 25, 50, 75, 90, 99])
    def test_percentiles_vs_numpy(self, q):
        rng = np.random.default_rng(0)
        samples = rng.gamma(2.0, 5.0, size=5000)  # ms-ish latency shape
        h = Histogram("lat_ms", buckets=DEFAULT_BUCKETS)
        for v in samples:
            h.observe(v)
        got = h.percentile(q)
        truth = float(np.percentile(samples, q))
        # accuracy bound = the owning bucket's width
        bounds = (0.0,) + DEFAULT_BUCKETS
        i = int(np.searchsorted(DEFAULT_BUCKETS, truth))
        i = min(i, len(DEFAULT_BUCKETS) - 1)
        width = bounds[i + 1] - bounds[i]
        assert abs(got - truth) <= width

    def test_percentile_edge_cases(self):
        h = Histogram("h", buckets=(1.0, 2.0))
        assert np.isnan(h.percentile(50))  # empty
        h.observe(100.0)                   # +Inf bucket
        assert h.percentile(50) == 2.0     # clamps to last finite bound
        with pytest.raises(ValueError):
            h.percentile(101)

    def test_pow2_buckets(self):
        assert pow2_buckets(1, 16) == (1.0, 2.0, 4.0, 8.0, 16.0)
        assert pow2_buckets(1, 10) == (1.0, 2.0, 4.0, 8.0, 10.0)


class TestExporters:
    def _registry(self):
        m = Metrics(enabled=True)
        m.counter("reqs_total", help="total requests", impl="pallas").inc(3)
        m.gauge("depth_now").set(5)
        h = m.histogram("wait_ms", buckets=(1.0, 10.0), help="queue wait")
        h.observe(0.5)
        h.observe(4.0)
        h.observe(50.0)
        return m

    def test_snapshot_json(self):
        snap = json.loads(self._registry().to_json())
        (c,) = snap["counters"]
        assert c == {"name": "reqs_total", "labels": {"impl": "pallas"},
                     "value": 3.0}
        (h,) = snap["histograms"]
        assert h["count"] == 3 and h["sum"] == 54.5
        assert h["bucket_counts"] == [1, 1, 1]
        assert h["p50"] is not None and h["p99"] == 10.0

    def test_prometheus_text_format(self):
        text = self._registry().to_prometheus()
        assert "# HELP reqs_total total requests" in text
        assert "# TYPE reqs_total counter" in text
        assert 'reqs_total{impl="pallas"} 3.0' in text
        assert "# TYPE wait_ms histogram" in text
        # cumulative buckets, integer-formatted bounds, +Inf == _count
        assert 'wait_ms_bucket{le="1"} 1' in text
        assert 'wait_ms_bucket{le="10"} 2' in text
        assert 'wait_ms_bucket{le="+Inf"} 3' in text
        assert "wait_ms_sum 54.5" in text
        assert "wait_ms_count 3" in text
        assert text.endswith("\n")

    def test_prometheus_headers_once_per_name(self):
        m = Metrics(enabled=True)
        m.counter("c", help="h", impl="a").inc()
        m.counter("c", help="h", impl="b").inc()
        text = m.to_prometheus()
        assert text.count("# TYPE c counter") == 1


# ---------------------------------------------------------------------------
# thread safety
# ---------------------------------------------------------------------------

class TestThreadSafety:
    N_THREADS = 8
    N_OPS = 2000

    def test_concurrent_counter_exact(self):
        m = Metrics(enabled=True)

        def work():
            # re-fetch per call like real instrumentation sites do
            for _ in range(self.N_OPS):
                m.counter("hits_total").inc()

        threads = [threading.Thread(target=work)
                   for _ in range(self.N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert m.counter("hits_total").value == self.N_THREADS * self.N_OPS

    def test_concurrent_histogram_exact(self):
        m = Metrics(enabled=True)
        h = m.histogram("obs_ms", buckets=(1.0, 2.0, 4.0))

        def work(seed):
            rng = np.random.default_rng(seed)
            for v in rng.uniform(0, 5, self.N_OPS):
                h.observe(float(v))

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(self.N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = self.N_THREADS * self.N_OPS
        assert h.count == total
        assert sum(h.bucket_counts()) == total

    def test_concurrent_spans_all_recorded(self):
        tr = Tracer(capacity=1 << 16, enabled=True)

        def work(tid):
            for i in range(200):
                with tr.span(f"t{tid}/op", i=i):
                    pass

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(self.N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        evts = tr.events()
        assert len(evts) == self.N_THREADS * 200
        # per-thread nesting depth stayed flat (thread-local depth)
        assert all(e["depth"] == 0 for e in evts)
        assert validate_chrome_trace(tr.to_chrome()) == []


# ---------------------------------------------------------------------------
# the global facade
# ---------------------------------------------------------------------------

class TestFacade:
    def test_configure_and_export_all(self, tmp_path):
        from repro import obs
        was_t, was_m = obs.trace_enabled(), obs.metrics_enabled()
        try:
            obs.configure(trace=True, metrics_on=True, clear=True)
            with obs.tracer().span("facade/x", a=1):
                pass
            obs.metrics().counter("facade_total").inc()
            tpath = str(tmp_path / "trace.json")
            mpath = str(tmp_path / "metrics.prom")
            written = obs.export_all(trace_path=tpath, metrics_path=mpath)
            assert written == [tpath, mpath]
            with open(tpath) as f:
                assert validate_chrome_trace(json.load(f)) == []
            with open(mpath) as f:
                assert "facade_total 1.0" in f.read()
        finally:
            obs.configure(trace=was_t, metrics_on=was_m, clear=True)

    def test_export_all_disabled_writes_nothing(self, tmp_path):
        from repro import obs
        was_t, was_m = obs.trace_enabled(), obs.metrics_enabled()
        try:
            obs.configure(trace=False, metrics_on=False)
            assert obs.export_all(
                trace_path=str(tmp_path / "t.json"),
                metrics_path=str(tmp_path / "m.prom")) == []
            assert list(tmp_path.iterdir()) == []
        finally:
            obs.configure(trace=was_t, metrics_on=was_m)


class TestDispatchMarker:
    """Kernel trace markers fire once per jit trace, carry no roofline
    prediction, and name the implementation traced (compiled Pallas,
    interpreted Pallas or the jnp reference); the device peaks stay keyed
    by device kind for the benchmark's roofline shares."""

    def test_peaks_are_keyed_by_device_kind(self):
        from repro.roofline.analysis import device_peaks

        assert device_peaks("TPU v5 lite")["hbm_bw"] == 819e9
        assert device_peaks("cpu") is None

    @pytest.mark.parametrize("kernels,impl", [("pallas", "interpret"),
                                              ("jnp", "ref")])
    def test_marker_on_an_untabled_device(self, kernels, impl, monkeypatch):
        import jax.numpy as jnp

        from repro import obs
        from repro.core import packing
        from repro.core.alphabet import DNA
        from repro.kernels import ops

        monkeypatch.setenv("REPRO_KERNELS", kernels)
        pt = packing.pack_text(DNA.random_string(300, seed=1), DNA, extra=40)
        was_t, was_m = obs.trace_enabled(), obs.metrics_enabled()
        try:
            obs.configure(trace=True, metrics_on=True, clear=True)
            ops.range_gather_words(pt, jnp.arange(8, dtype=jnp.int32), 16)
            marks = [e["args"] for e in obs.tracer().events()
                     if e["name"] == "kernel/range_gather/trace"]
            counts = {c["labels"]["impl"]: c["value"]
                      for c in obs.metrics().snapshot()["counters"]
                      if c["name"] == "kernel_traces_total"}
        finally:
            obs.configure(trace=was_t, metrics_on=was_m, clear=True)
        assert [m["impl"] for m in marks] == [impl]
        assert marks[0]["rows"] == 8
        assert not any(k.startswith("roofline") for k in marks[0])
        assert counts == {impl: 1}


# ---------------------------------------------------------------------------
# one clock with the device trace: spans as profiler annotations
# ---------------------------------------------------------------------------

def _host_events(directory) -> list[tuple[str, float, float]]:
    """(name, start_ns, end_ns) of every event on the host planes of the
    newest profiler trace under ``directory``."""
    import glob
    import os

    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


class TestProfilerClock:
    def test_enabled_span_is_on_the_host_plane(self, tmp_path):
        """An enabled span shows up by name in the profiler's host plane,
        inside the window annotation that encloses it, and its child
        nests inside it; the timed-after-the-fact events do not."""
        import jax

        tr = Tracer(enabled=True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("bench/window"):
                with tr.span("prepare/step", w=16):
                    with tr.span("prepare/readback"):
                        sum(range(1000))
                tr.complete("serve/queue_wait", 0, 10)
                tr.instant("kernel/x/trace")
        finally:
            jax.profiler.stop_trace()
        events = _host_events(str(tmp_path))
        by_name = {}
        for name, a, b in events:
            by_name.setdefault(name, []).append((a, b))
        (win,) = by_name["bench/window"]
        (step,) = by_name["prepare/step"]
        (back,) = by_name["prepare/readback"]
        assert win[0] <= step[0] <= back[0] <= back[1] <= step[1] <= win[1]
        assert "serve/queue_wait" not in by_name
        assert "kernel/x/trace" not in by_name
        assert [e["name"] for e in tr.events()] == [
            "prepare/readback", "prepare/step", "serve/queue_wait",
            "kernel/x/trace"]

    def test_annotation_closes_when_the_block_raises(self, tmp_path):
        import jax

        tr = Tracer(enabled=True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("bench/window"):
                with pytest.raises(KeyError):
                    with tr.span("build/flatten"):
                        raise KeyError("boom")
                with tr.span("build/after"):
                    pass
        finally:
            jax.profiler.stop_trace()
        spans = {n: (a, b) for n, a, b in _host_events(str(tmp_path))}
        assert spans["build/flatten"][1] <= spans["build/after"][0]
        assert [e["name"] for e in tr.events()] == ["build/flatten",
                                                    "build/after"]

    def test_disabled_span_imports_no_profiler(self, monkeypatch):
        """The disabled path is one attribute check: NULL_SPAN, and no
        import of ``jax.profiler`` (here made to fail if tried)."""
        import sys

        from repro.obs import trace as trace_mod

        monkeypatch.setattr(trace_mod, "_annotation", None)
        monkeypatch.setitem(sys.modules, "jax.profiler", None)
        tr = Tracer(enabled=False)
        with tr.span("serve/route", rows=3) as sp:
            sp.set(more=1)
        assert tr.span("serve/scatter") is NULL_SPAN
        assert trace_mod._annotation is None
        assert tr.events() == []

    def test_obs_imports_without_jax(self):
        import subprocess
        import sys
        from pathlib import Path

        src = Path(__file__).resolve().parents[1] / "src"
        code = ("import sys; sys.modules['jax'] = None; "
                "from repro.obs.trace import Tracer, NULL_SPAN; "
                "t = Tracer(enabled=True); "
                "[t.span('a').__enter__().__exit__(None, None, None)]; "
                "assert [e['name'] for e in t.events()] == ['a']; "
                "assert Tracer(enabled=False).span('b') is NULL_SPAN")
        res = subprocess.run([sys.executable, "-c", code], cwd=src,
                             capture_output=True, text=True, timeout=120)
        assert res.returncode == 0, res.stderr


# ---------------------------------------------------------------------------
# where the program's spans are: build, prepare loop, server
# ---------------------------------------------------------------------------

def _inside(child: dict, parent: dict) -> bool:
    return (child["tid"] == parent["tid"]
            and child["depth"] > parent["depth"]
            and parent["ts_ns"] <= child["ts_ns"]
            and child["ts_ns"] + child["dur_ns"]
            <= parent["ts_ns"] + parent["dur_ns"])


@pytest.fixture
def traced():
    from repro import obs
    was_t, was_m = obs.trace_enabled(), obs.metrics_enabled()
    obs.configure(trace=True, metrics_on=True, clear=True)
    try:
        yield obs
    finally:
        obs.configure(trace=was_t, metrics_on=was_m, clear=True)


def _small_dna(n=3000):
    from repro.core.alphabet import DNA
    from repro.core.api import EraConfig, EraIndexer

    s = DNA.random_string(n, seed=11)
    # reads of 4 symbols: the prepare loop takes more than one iteration
    return s, EraIndexer(DNA, EraConfig(memory_bytes=2048, w_max=4,
                                        build_impl="none"))


class TestProgramSpans:
    def test_build_device_spans_nest(self, traced):
        s, ix = _small_dna()
        ix.build_device(s, max_pattern_len=32)
        ev = traced.tracer().events()
        named = lambda n: [e for e in ev if e["name"] == n]  # noqa: E731
        (total,) = named("build/total")
        (text,) = named("build/device_text")
        (flat,) = named("build/flatten")
        (loop,) = named("prepare/batch_loop")
        assert all(_inside(e, total) for e in (text, flat, loop))
        steps, backs = named("prepare/step"), named("prepare/readback")
        assert len(steps) == len(backs) == loop["args"]["iterations"] > 1
        for st, rb in zip(steps, backs):
            assert _inside(st, loop) and _inside(rb, st)
        assert text["ts_ns"] + text["dur_ns"] <= loop["ts_ns"]
        assert loop["ts_ns"] + loop["dur_ns"] <= flat["ts_ns"]

    def test_serial_prepare_reads_back_inside_the_step(self, traced):
        from repro.core.api import EraConfig, EraIndexer
        from repro.core.alphabet import DNA

        s = DNA.random_string(1500, seed=3)
        EraIndexer(DNA, EraConfig(memory_bytes=2048, build_impl="none",
                                  w_max=4, construction="serial")).build(s)
        ev = traced.tracer().events()
        steps = [e for e in ev if e["name"] == "prepare/step"]
        backs = [e for e in ev if e["name"] == "prepare/readback"]
        assert steps and len(steps) == len(backs)
        assert all(_inside(rb, st) for st, rb in zip(steps, backs))

    @pytest.mark.parametrize("shards", [0, 2])
    def test_server_spans_one_per_batch(self, traced, shards):
        from repro.launch.serving import AsyncServer, ServeConfig

        s, ix = _small_dna()
        dev = (ix.build_sharded(s, n_shards=shards, max_pattern_len=32)
               if shards else ix.build_device(s, max_pattern_len=32))
        rng = np.random.default_rng(0)
        starts = rng.integers(0, len(s) - 40, size=96)
        pats = [np.asarray(s[i:i + 8 + i % 9], np.int32) for i in starts]
        pats += pats[:24]                          # cache hits and dups
        server = AsyncServer(dev, ServeConfig(max_batch=16, cache_size=64,
                                              pipeline=True))
        traced.tracer().clear()
        server.serve(pats)
        ev = traced.tracer().events()
        count = lambda n: sum(e["name"] == n for e in ev)  # noqa: E731
        assert server.n_batches >= len(pats) // 16
        assert count("serve/route") == server.n_batches
        assert count("serve/scatter") == server.n_batches
        assert count("serve/queue_wait") == server.n_batches
        batches = {c["name"]: c["value"]
                   for c in traced.metrics().snapshot()["counters"]}
        assert batches["serve_batches_total"] == server.n_batches
        # route precedes a batch's pad/packs (one a shard); the scatter
        # follows its syncs
        seq = [e["name"] for e in sorted(ev, key=lambda e: e["ts_ns"])
               if e["name"] in ("serve/route", "serve/pad_pack",
                                "serve/consume_sync", "serve/scatter")]
        for a, b in zip(seq, seq[1:]):
            if b == "serve/pad_pack":
                assert a == "serve/route" or (shards and a == b)
            if a == "serve/consume_sync":
                assert b == "serve/scatter" or (shards and a == b)

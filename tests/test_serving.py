"""Continuous-batching serving stack: correctness and accounting.

The serving tier must be a pure throughput/latency optimization — every
mode (sync baseline, overlapped pipeline, hot-prefix cache, fused
find-and-fetch) returns byte-identical results to ``DeviceIndex.find_batch``
/ the per-pattern oracle.  These tests pin that invariant plus the
bookkeeping the benchmarks report: admission-queue overflow, cache
hit/miss/eviction counters, and the env-var ``ServeConfig`` idiom.
"""

import collections
import time

import numpy as np
import pytest

from repro.core.alphabet import DNA, PROTEIN_CLASS
from repro.core.api import EraConfig, EraIndexer
from repro.core.query import RouteCache
from repro.launch.serving import (
    AsyncServer,
    ServeConfig,
    make_hot_workload,
    run_closed_loop,
)


@pytest.fixture(scope="module")
def dev_and_s():
    alpha = DNA
    s = alpha.random_string(4000, seed=11)
    dev = EraIndexer(alpha, EraConfig(
        memory_bytes=1 << 16, build_impl="none",
        packing="dense")).build_device(s, max_pattern_len=64)
    return dev, s


@pytest.fixture()
def obs_on():
    from repro import obs
    was_t, was_m = obs.trace_enabled(), obs.metrics_enabled()
    obs.configure(trace=True, metrics_on=True, clear=True)
    yield obs
    obs.configure(trace=was_t, metrics_on=was_m, clear=True)


@pytest.fixture(scope="module")
def workload(dev_and_s):
    _, s = dev_and_s
    rng = np.random.default_rng(3)
    return make_hot_workload(s, rng, n_requests=300, hot_pool=12,
                             hot_frac=0.7, min_len=2, max_len=18,
                             n_symbols=4)


class TestServeConfig:
    def test_env_defaults(self, monkeypatch):
        monkeypatch.setenv("REPRO_SERVE_MAX_BATCH", "64")
        monkeypatch.setenv("REPRO_SERVE_CACHE", "17")
        monkeypatch.setenv("REPRO_SERVE_PIPELINE", "0")
        monkeypatch.setenv("REPRO_SERVE_FETCH", "8")
        monkeypatch.setenv("REPRO_SERVE_QUEUE_DEPTH", "99")
        monkeypatch.setenv("REPRO_SERVE_MAX_WAIT_MS", "2.5")
        cfg = ServeConfig()
        assert cfg.max_batch == 64 and cfg.cache_size == 17
        assert cfg.pipeline is False and cfg.fetch == 8
        assert cfg.queue_depth == 99 and cfg.max_wait_ms == 2.5

    def test_overrides_beat_env(self, monkeypatch):
        monkeypatch.setenv("REPRO_SERVE_MAX_BATCH", "64")
        assert ServeConfig(max_batch=8).max_batch == 8

    def test_rejects_unknown_and_invalid(self):
        with pytest.raises(TypeError):
            ServeConfig(not_a_knob=1)
        with pytest.raises(ValueError):
            ServeConfig(max_batch=0)
        with pytest.raises(ValueError):
            ServeConfig(fetch=6)  # not a multiple of 4


class TestModesByteIdentical:
    MODES = [
        dict(pipeline=False, cache_size=0),   # sync baseline
        dict(pipeline=True, cache_size=0),    # overlapped pipeline
        dict(pipeline=True, cache_size=256),  # pipeline + cache
        dict(pipeline=True, cache_size=256, max_batch=16, queue_depth=32),
    ]

    def test_all_modes_match_find_batch(self, dev_and_s, workload):
        dev, _ = dev_and_s
        want = dev.find_batch(workload)
        for kw in self.MODES:
            res, _ = run_closed_loop(dev, workload, ServeConfig(**kw))
            assert len(res) == len(workload)
            for (pos, win), w in zip(res, want):
                np.testing.assert_array_equal(pos, w, err_msg=str(kw))
                assert win is None

    def test_fetch_modes_match_find_fetch_batch(self, dev_and_s, workload):
        dev, _ = dev_and_s
        pats = workload[:80]
        ranges, wins = dev.find_fetch_batch(pats, fetch=16)
        for kw in (dict(pipeline=False, cache_size=0, fetch=16),
                   dict(pipeline=True, cache_size=128, fetch=16)):
            res, _ = run_closed_loop(dev, pats, ServeConfig(**kw))
            for i, (pos, win) in enumerate(res):
                np.testing.assert_array_equal(pos, ranges[i], err_msg=str(kw))
                np.testing.assert_array_equal(win, wins[i], err_msg=str(kw))

    def test_cache_on_off_identical(self, dev_and_s, workload):
        # small batches: the pipeline dispatches batch k+1 before batch
        # k's consume populates the cache, so hits need several batches
        dev, _ = dev_and_s
        on, st_on = run_closed_loop(
            dev, workload, ServeConfig(pipeline=True, cache_size=512,
                                       max_batch=32))
        off, _ = run_closed_loop(
            dev, workload, ServeConfig(pipeline=True, cache_size=0,
                                       max_batch=32))
        for (p1, _), (p2, _) in zip(on, off):
            np.testing.assert_array_equal(p1, p2)
        assert st_on["cache"]["hits"] > 0


class TestAdmissionQueue:
    def test_overflow_rejects_and_counts(self, dev_and_s):
        dev, s = dev_and_s
        server = AsyncServer(dev, ServeConfig(queue_depth=4, pipeline=False,
                                              cache_size=0))
        pat = np.asarray(s[:6])
        accepted = [server.submit(i, pat) for i in range(7)]
        assert accepted == [True] * 4 + [False] * 3
        assert server.n_admitted == 4 and server.n_rejected == 3
        server.drain()
        assert len(server.results) == 4

    def test_closed_loop_retries_rejections(self, dev_and_s, workload):
        dev, _ = dev_and_s
        res, stats = run_closed_loop(
            dev, workload, ServeConfig(queue_depth=8, max_batch=8,
                                       pipeline=True, cache_size=0))
        assert stats["served"] == len(workload)
        want = dev.find_batch(workload)
        for (pos, _), w in zip(res, want):
            np.testing.assert_array_equal(pos, w)

    def test_shapes_are_bucketed_pow2(self, dev_and_s, workload):
        dev, _ = dev_and_s
        _, stats = run_closed_loop(dev, workload,
                                   ServeConfig(pipeline=True, cache_size=0))
        for m_pad, b_pad in stats["shapes"]:
            assert m_pad & (m_pad - 1) == 0 or m_pad == dev.max_pattern_len
            assert b_pad & (b_pad - 1) == 0


class TestRouteCache:
    def test_lru_eviction_and_counters(self):
        c = RouteCache(capacity=2)
        c.put("a", (0, 1))
        c.put("b", (1, 2))
        assert c.get("a") == (0, 1)   # refresh a
        c.put("c", (2, 3))            # evicts b (LRU)
        assert c.get("b") is None
        assert c.get("a") == (0, 1) and c.get("c") == (2, 3)
        assert c.evictions == 1 and c.hits == 3 and c.misses == 1
        assert 0 < c.hit_rate < 1
        c.clear()
        assert len(c) == 0

    def test_zero_capacity_never_stores(self):
        c = RouteCache(capacity=0)
        c.put("a", (0, 1))
        assert c.get("a") is None and len(c) == 0

    def test_find_batch_cached_identity_and_counters(self, dev_and_s,
                                                     workload):
        dev, _ = dev_and_s
        pats = workload[:60]
        want = dev.find_batch(pats)
        cache = RouteCache(capacity=128)
        for _ in range(2):
            got = dev.find_batch_cached(pats, cache)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(g, w)
        assert cache.hits > 0 and cache.misses > 0
        stats = cache.stats()
        assert stats["hits"] == cache.hits and stats["size"] == len(cache)

    def test_eviction_pressure_stays_correct(self, dev_and_s, workload):
        dev, _ = dev_and_s
        pats = workload[:60]
        want = dev.find_batch(pats)
        cache = RouteCache(capacity=3)
        got = dev.find_batch_cached(pats * 2, cache)
        for g, w in zip(got, want * 2):
            np.testing.assert_array_equal(g, w)
        assert cache.evictions > 0 and len(cache) <= 3

    @staticmethod
    def _plain_lru_holds(capacity, puts_and_gets, key):
        """Whether a plain LRU of ``capacity`` still holds ``key`` after
        ``puts_and_gets`` (("put"|"get", key) pairs)."""
        lru = collections.OrderedDict()
        for op, k in puts_and_gets:
            if op == "get":
                if k in lru:
                    lru.move_to_end(k)
                continue
            lru[k] = None
            lru.move_to_end(k)
            while len(lru) > capacity:
                lru.popitem(last=False)
        return key in lru

    def test_promoted_entry_survives_scan(self):
        c = RouteCache(capacity=10)
        ops = [("put", "hot"), ("get", "hot")]
        ops += [("put", f"once{i}") for i in range(c.capacity)]
        for op, k in ops:
            if op == "put":
                c.put(k, (0, 1))
            else:
                c.get(k)
        assert c.get("hot") == (0, 1)
        assert not self._plain_lru_holds(c.capacity, ops, "hot")
        assert c.promotions == 1 and c.demotions == 0
        assert c.evictions == 1 and len(c) == c.capacity

    def test_probation_hit_promotes_overflow_demotes(self):
        c = RouteCache(capacity=5)       # protected holds 5 - max(1, 1) = 4
        assert c.protected_capacity == 4
        for i in range(5):
            c.put(i, (i, 1))
        for i in range(5):               # the fifth promotion demotes 0
            assert c.get(i) == (i, 1)
        st = c.stats()
        assert (st["promotions"], st["demotions"], st["evictions"]) \
            == (5, 1, 0)
        assert st["protected"] == 4 and st["size"] == 5
        c.put("new", (9, 9))             # evicts the demoted 0 first
        assert c.evictions == 1 and c.get(0) is None
        assert all(c.get(i) == (i, 1) for i in range(1, 5))
        assert c.get("new") == (9, 9)    # promoted, demotes 1
        st = c.stats()
        assert (st["promotions"], st["demotions"], st["evictions"]) \
            == (6, 2, 1)
        assert st["protected"] == 4 and len(c) == 5

    def test_put_of_held_key_keeps_segment(self):
        c = RouteCache(capacity=5)
        c.put("p", (0, 1))
        c.get("p")                       # protected
        c.put("q", (1, 1))               # probation
        c.put("p", (2, 2))
        c.put("q", (3, 3))
        assert c.stats()["protected"] == 1 and len(c) == 2
        assert c.promotions == 1 and c.evictions == 0
        assert c.get("p") == (2, 2) and c.promotions == 1
        assert c.get("q") == (3, 3) and c.promotions == 2

    def test_capacity_one_stores_and_evicts(self):
        c = RouteCache(capacity=1)
        assert c.protected_capacity == 0
        c.put("a", (0, 1))
        assert c.get("a") == (0, 1) and c.get("a") == (0, 1)
        c.put("b", (1, 1))
        assert c.get("a") is None and c.get("b") == (1, 1)
        st = c.stats()
        assert (st["hits"], st["misses"], st["evictions"]) == (3, 1, 1)
        assert (st["promotions"], st["demotions"], st["protected"]) \
            == (0, 0, 0)
        assert len(c) == 1


class TestScanResistantServing:
    BATCH, CACHE = 16, 32
    HOT_BATCHES = (0, 2, 6, 10, 14)  # gaps of 3-4 batches of one-offs

    def _stream(self, s):
        """One-off patterns (distinct, never repeated) with one short,
        high-count pattern first in the batches ``HOT_BATCHES``: after its
        first repeat, more one-offs pass between its uses than the whole
        cache holds."""
        rng = np.random.default_rng(21)
        hot = np.asarray(s[100:103], np.int32)
        seen, pats = set(), []
        for b in range(max(self.HOT_BATCHES) + 1):
            if b in self.HOT_BATCHES:
                pats.append(hot)
            while len(pats) < (b + 1) * self.BATCH:
                i, m = int(rng.integers(0, len(s) - 20)), int(
                    rng.integers(8, 17))
                p = np.asarray(s[i : i + m], np.int32)
                if p.tobytes() not in seen:
                    seen.add(p.tobytes())
                    pats.append(p)
        return hot, pats

    def test_recurring_pattern_stays_a_hit(self, dev_and_s, obs_on):
        dev, s = dev_and_s
        hot, pats = self._stream(s)
        assert len(dev.find_batch([hot])[0]) > 40
        gaps = np.diff(self.HOT_BATCHES[1:]) * self.BATCH
        assert (gaps > self.CACHE).all()  # plain LRU would evict it
        kw = dict(pipeline=True, max_batch=self.BATCH, max_wait_ms=0.0)
        off, _ = run_closed_loop(dev, pats, ServeConfig(cache_size=0, **kw))
        # second, so the registry's gauges read this server
        on, st = run_closed_loop(dev, pats,
                                 ServeConfig(cache_size=self.CACHE, **kw))
        for (p_on, _), (p_off, _), w in zip(on, off, dev.find_batch(pats)):
            assert p_on.tobytes() == p_off.tobytes()
            np.testing.assert_array_equal(p_on, w)
        cache = st["cache"]
        # every use of the hot pattern after its first is a hit; one-offs
        # only ever miss
        assert cache["hits"] == len(self.HOT_BATCHES) - 1
        assert cache["misses"] == len(pats) - cache["hits"]
        assert cache["promotions"] == 1 and cache["protected"] == 1
        m = obs_on.metrics()
        assert m.counter("serve_cache_promotions_total").value \
            == cache["promotions"]
        assert m.gauge("serve_cache_protected").value == cache["protected"]


class TestPadBatchBuckets:
    def test_pinned_width_and_rows(self, dev_and_s):
        dev, s = dev_and_s
        pats = [np.asarray(s[:5]), np.asarray(s[3:10])]
        padded, lengths, route = dev.pad_batch(pats, m_pad=16, b_pad=8)
        assert padded.shape == (8, 16) and lengths.shape == (8,)
        assert (lengths[2:] == 1).all()  # dummy rows
        st, ct = dev.find_batch_ranges(padded, lengths, route)
        st2, ct2 = dev.find_batch_ranges(*dev.pad_batch(pats))
        np.testing.assert_array_equal(np.asarray(st)[:2], np.asarray(st2))
        np.testing.assert_array_equal(np.asarray(ct)[:2], np.asarray(ct2))

    def test_rejects_bad_buckets(self, dev_and_s):
        dev, s = dev_and_s
        pats = [np.asarray(s[:10])]
        with pytest.raises(ValueError):
            dev.pad_batch(pats, m_pad=6)    # not a multiple of 4
        with pytest.raises(ValueError):
            dev.pad_batch(pats, m_pad=8)    # below the natural width (12)
        with pytest.raises(ValueError):
            dev.pad_batch(pats, b_pad=0)    # fewer rows than patterns


class TestFindFetch:
    def test_windows_match_read_symbols(self, dev_and_s, workload):
        dev, _ = dev_and_s
        pats = workload[:40]
        padded, lengths, route = dev.pad_batch(pats)
        start, count = map(np.asarray,
                           dev.find_batch_ranges(padded, lengths, route))
        _, wins = dev.find_fetch_batch(pats, fetch=16)
        pos0 = dev.ell_host[np.clip(start, 0, dev.n_leaves - 1)]
        ref = np.asarray(dev.read_symbols(pos0, 16))
        n_real = dev.n_leaves
        for i in range(len(pats)):
            if count[i] == 0:
                assert (wins[i] == -1).all()
                continue
            past = pos0[i] + np.arange(16) >= n_real
            np.testing.assert_array_equal(wins[i][~past], ref[i][~past])
            assert (wins[i][past] == dev.s_text.terminal).all()

    def test_dense_and_byte_windows_identical(self):
        alpha = PROTEIN_CLASS
        s = alpha.random_string(1200, seed=5)
        idx = EraIndexer(alpha, EraConfig(
            memory_bytes=1 << 16, build_impl="none")).build(s)
        rng = np.random.default_rng(8)
        pats = [np.asarray(s[i : i + m]) for i, m in zip(
            rng.integers(0, 1100, 12), rng.integers(1, 14, 12))]
        r_d, w_d = idx.to_device(packing="dense").find_fetch_batch(
            pats, fetch=20)
        r_b, w_b = idx.to_device(packing="bytes").find_fetch_batch(
            pats, fetch=20)
        for a, b in zip(r_d, r_b):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(w_d, w_b)

    def test_fetch_validation(self, dev_and_s):
        dev, s = dev_and_s
        with pytest.raises(ValueError):
            dev.find_fetch_batch([np.asarray(s[:4])], fetch=6)
        with pytest.raises(ValueError):
            dev.find_fetch_batch([np.asarray(s[:4])],
                                 fetch=dev.max_pattern_len + 4)


class TestBatchAging:
    """``max_wait_ms`` is per-request batch aging: a partial batch is held
    open until the OLDEST queued request has waited that long, then
    dispatched whatever its size.  (It used to be dead config.)"""

    def test_partial_batch_held_until_age(self, dev_and_s):
        dev, s = dev_and_s
        server = AsyncServer(dev, ServeConfig(
            pipeline=False, cache_size=0, max_batch=8, max_wait_ms=60.0))
        server.submit(0, np.asarray(s[:6]))
        server.submit(1, np.asarray(s[2:8]))
        assert server.pump() is False          # young partial batch: held
        assert server.results == {} and len(server.queue) == 2
        time.sleep(0.08)                       # let the oldest request age
        assert server.pump() is True
        assert sorted(server.results) == [0, 1]
        assert server.n_batches == 1

    def test_full_batch_dispatches_immediately(self, dev_and_s, workload):
        dev, _ = dev_and_s
        server = AsyncServer(dev, ServeConfig(
            pipeline=False, cache_size=0, max_batch=4, max_wait_ms=1e6))
        for i, p in enumerate(workload[:4]):
            server.submit(i, p)
        assert server.pump() is True           # full: aging never consulted
        assert len(server.results) == 4

    def test_drain_terminates_on_aging(self, dev_and_s, workload):
        dev, _ = dev_and_s
        server = AsyncServer(dev, ServeConfig(
            pipeline=True, cache_size=0, max_batch=64, max_wait_ms=5.0))
        for i, p in enumerate(workload[:10]):  # never fills max_batch
            server.submit(i, p)
        server.drain()
        assert len(server.results) == 10 and server.inflight is None

    def test_aged_results_byte_identical(self, dev_and_s, workload):
        dev, _ = dev_and_s
        pats = workload[:10]
        want = dev.find_batch(pats)
        res, _ = run_closed_loop(dev, pats, ServeConfig(
            pipeline=True, cache_size=0, max_batch=64, max_wait_ms=2.0))
        for (pos, _), w in zip(res, want):
            np.testing.assert_array_equal(pos, w)


class TestObservabilityWiring:
    """The serving loop's instrumentation: counters/histograms/spans land
    in the global registry when obs is on, results stay byte-identical,
    and with obs off the server binds only null instruments."""

    def test_registry_wiring_closed_loop(self, dev_and_s, workload, obs_on):
        dev, _ = dev_and_s
        res, stats = run_closed_loop(dev, workload, ServeConfig(
            pipeline=True, cache_size=512, max_batch=32))
        m = obs_on.metrics()
        assert m.counter("serve_requests_total").value >= len(workload)
        assert m.counter("serve_batches_total").value == stats["batches"]
        assert m.counter("serve_cache_hits_total").value \
            == stats["cache"]["hits"]
        fill = m.histogram("serve_batch_fill")
        assert fill.count == stats["batches"]
        assert m.histogram("serve_batch_age_ms").count > 0
        assert m.histogram("serve_queue_wait_ms").count >= len(workload)
        prom = m.to_prometheus()
        assert "serve_cache_hit_rate" in prom
        assert "serve_batch_fill_bucket" in prom

    def test_spans_and_byte_identity(self, dev_and_s, workload, obs_on):
        dev, _ = dev_and_s
        want = dev.find_batch(workload)
        res, _ = run_closed_loop(dev, workload, ServeConfig(
            pipeline=True, cache_size=0, max_batch=32))
        for (pos, _), w in zip(res, want):
            np.testing.assert_array_equal(pos, w)
        names = {e["name"] for e in obs_on.tracer().events()}
        for want_span in ("serve/queue_wait", "serve/pad_pack",
                          "serve/device_dispatch", "serve/consume_sync"):
            assert want_span in names, names
        assert obs_on.validate_chrome_trace(
            obs_on.tracer().to_chrome()) == []

    def test_obs_off_binds_null_instruments(self, dev_and_s):
        from repro import obs
        was_t, was_m = obs.trace_enabled(), obs.metrics_enabled()
        obs.configure(trace=False, metrics_on=False)
        try:
            dev, _ = dev_and_s
            server = AsyncServer(dev, ServeConfig(pipeline=True))
            assert server._m_requests is obs.NULL_INSTRUMENT
            assert server._h_batch_fill is obs.NULL_INSTRUMENT
            assert server._trace_on is False and server._metrics_on is False
        finally:
            obs.configure(trace=was_t, metrics_on=was_m)

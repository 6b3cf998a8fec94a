"""The sharded index fabric must be a pure distribution transform.

Construction: :func:`repro.core.fabric.sharded_prepare` (shard_map over a
device mesh, per-shard convergence mask, fused sort key, tail compaction)
must produce the SAME final (G, F) state — ``L``/``b_off``/``b_c1``/
``b_c2`` bit-identical — as the single-device batched engine, across
alphabets, uneven group splits, and the 1-shard degenerate mesh.

Queries: :class:`repro.core.fabric.ShardedIndex` (route-key shards +
replicated route table) must answer ``find_batch`` / ``find_fetch_batch``
identically to one :class:`DeviceIndex` over the whole string, including
patterns short enough to span a shard boundary, and round-trip through
per-shard npz archives.

On a single-device host everything still runs (mesh of one); the CI
fabric leg re-runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the multi-shard
mesh paths execute for real.
"""

import os

import jax
import numpy as np
import pytest

from repro.core import fabric
from repro.core.api import EraConfig, EraIndexer
from repro.core.prepare import subtree_prepare_batch
from repro.core.query import DeviceIndex, route_depth, shard_npz_path
from repro.data.strings import dataset

STATE_FIELDS = ("L", "start", "area", "b_off", "b_c1", "b_c2")
multi_device = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs a simulated mesh (XLA_FLAGS="
           "--xla_force_host_platform_device_count=N)")


def _workload(name, n, mem):
    s, alpha = dataset(name, n, seed=0)
    cfg = EraConfig(memory_bytes=mem, r_bytes=512, build_impl="none")
    ix = EraIndexer(alpha, cfg)
    groups = ix.partition(s)
    return s, alpha, ix, groups, ix._capacity(groups), ix._device_text(s)


def _assert_states_equal(ref, got):
    for field in STATE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, field)), np.asarray(getattr(got, field)),
            err_msg=field)


class TestFusedSortKey:
    """sort_fuse packs (major, window, tie) into the fewest uint32 lanes;
    the engine must not notice."""

    @pytest.mark.parametrize("name,n,mem", [
        ("dna", 6_000, 4096),       # 1-lane fused key on small w
        ("protein", 4_000, 8192),
        ("byte", 3_000, 8192),      # codes >= 128: unsigned order
    ])
    def test_bit_identical(self, name, n, mem):
        _, _, ix, groups, cap, sp = _workload(name, n, mem)
        ecfg = ix.config.elastic_config()
        ref = subtree_prepare_batch(sp, groups, cap, ecfg, sort_fuse=False)
        got = subtree_prepare_batch(sp, groups, cap, ecfg, sort_fuse=True)
        _assert_states_equal(ref, got)


class TestShardedPrepare:
    @pytest.mark.parametrize("name,n,mem", [
        ("dna", 6_000, 4096),
        ("protein", 4_000, 8192),
        ("byte", 3_000, 8192),
    ])
    def test_bit_identical(self, name, n, mem):
        _, _, ix, groups, cap, sp = _workload(name, n, mem)
        ecfg = ix.config.elastic_config()
        ref = subtree_prepare_batch(sp, groups, cap, ecfg)
        got = fabric.sharded_prepare(sp, groups, cap, ecfg)
        _assert_states_equal(ref, got)

    def test_compaction_off_bit_identical(self):
        """``compact=False`` (EraConfig.compaction through build_sharded)
        runs every iteration full width — same final state."""
        _, _, ix, groups, cap, sp = _workload("dna", 6_000, 4096)
        ecfg = ix.config.elastic_config()
        ref = subtree_prepare_batch(sp, groups, cap, ecfg)
        got = fabric.sharded_prepare(sp, groups, cap, ecfg, compact=False)
        _assert_states_equal(ref, got)

    def test_one_shard_degenerate_mesh(self):
        _, _, ix, groups, cap, sp = _workload("dna", 6_000, 4096)
        ecfg = ix.config.elastic_config()
        ref = subtree_prepare_batch(sp, groups, cap, ecfg)
        got = fabric.sharded_prepare(sp, groups, cap, ecfg,
                                     mesh=fabric.fabric_mesh(1))
        _assert_states_equal(ref, got)

    @multi_device
    def test_uneven_group_split(self):
        """G not divisible by the mesh: dummy born-converged padding
        groups must never leak into real results."""
        _, _, ix, groups, cap, sp = _workload("dna", 6_000, 4096)
        n_dev = min(4, jax.device_count())
        assert len(groups) % n_dev != 0 or len(groups) > n_dev
        ecfg = ix.config.elastic_config()
        ref = subtree_prepare_batch(sp, groups, cap, ecfg)
        got = fabric.sharded_prepare(sp, groups, cap, ecfg,
                                     mesh=fabric.fabric_mesh(n_dev))
        _assert_states_equal(ref, got)


def _pattern_mix(s, alpha, rng, k_route):
    """Planted + random patterns, including length < k_route so some
    spans cover several route cells (the shard fan-out path).
    ``alpha=None`` skips the random (possibly-missing) patterns."""
    pats = []
    for m in (2, 3, max(1, k_route - 1), k_route, k_route + 3, 12):
        for _ in range(4):
            i = int(rng.integers(0, len(s) - 1 - m))
            pats.append(np.asarray(s[i : i + m], np.int32))
            if alpha is not None:
                pats.append(rng.integers(0, alpha.base, size=m,
                                         dtype=np.int32))
    return pats


class TestShardedIndex:
    @pytest.mark.parametrize("name,n,mem,n_shards", [
        ("dna", 6_000, 4096, 4),
        ("protein", 4_000, 8192, 3),   # uneven entry split
        ("byte", 3_000, 8192, 2),
    ])
    def test_find_identical(self, name, n, mem, n_shards):
        s, alpha, ix, groups, cap, sp = _workload(name, n, mem)
        dev = ix.build_device(s, max_pattern_len=64)
        sh = ix.build_sharded(s, n_shards=n_shards, max_pattern_len=64)
        assert sh.n_shards >= 1
        assert sh.n_leaves == dev.ell.shape[0]
        rng = np.random.default_rng(3)
        pats = _pattern_mix(s, alpha, rng, sh.k_route)
        ref = dev.find_batch(pats)
        got = sh.find_batch(pats)
        for i, (a, b) in enumerate(zip(ref, got)):
            np.testing.assert_array_equal(a, b, err_msg=f"pattern {i}")
        ref_pos, ref_win = dev.find_fetch_batch(pats, fetch=8)
        got_pos, got_win = sh.find_fetch_batch(pats, fetch=8)
        for i, (a, b) in enumerate(zip(ref_pos, got_pos)):
            np.testing.assert_array_equal(a, b, err_msg=f"pattern {i}")
        np.testing.assert_array_equal(ref_win, got_win)

    def test_short_patterns_span_shards(self):
        """Some route spans must actually cross a shard cut, otherwise
        the fan-out/merge path went untested."""
        s, alpha, ix, *_ = _workload("dna", 6_000, 4096)
        sh = ix.build_sharded(s, n_shards=4, max_pattern_len=64)
        if sh.n_shards < 2:
            pytest.skip("route cells did not split")
        spans = [sh.shard_span(np.asarray([c], np.int32))
                 for c in range(alpha.base)]
        assert any(hi > lo for lo, hi in spans)

    def test_one_shard_index(self):
        s, alpha, ix, *_ = _workload("dna", 6_000, 4096)
        dev = ix.build_device(s, max_pattern_len=64)
        sh = ix.build_sharded(s, n_shards=1, max_pattern_len=64)
        assert sh.n_shards == 1
        rng = np.random.default_rng(5)
        pats = _pattern_mix(s, alpha, rng, sh.k_route)
        for a, b in zip(dev.find_batch(pats), sh.find_batch(pats)):
            np.testing.assert_array_equal(a, b)

    def test_route_depth_pinned_across_shards(self):
        s, _, ix, *_ = _workload("dna", 6_000, 4096)
        sh = ix.build_sharded(s, n_shards=4)
        assert len({d.k_route for d in sh.shards}) == 1
        assert sh.k_route == sh.shards[0].k_route

    def test_save_load_roundtrip(self, tmp_path):
        s, alpha, ix, *_ = _workload("dna", 6_000, 4096)
        sh = ix.build_sharded(s, n_shards=3, max_pattern_len=64)
        base = str(tmp_path / "fabric_idx")
        sh.save(base)
        files = fabric.ShardedIndex.shard_files(base)
        assert len(files) == sh.n_shards
        assert files[0] == shard_npz_path(base, 0)
        back = fabric.ShardedIndex.load(base)
        assert back.n_shards == sh.n_shards
        np.testing.assert_array_equal(back.cell_lo, sh.cell_lo)
        rng = np.random.default_rng(9)
        pats = _pattern_mix(s, alpha, rng, sh.k_route)
        for a, b in zip(sh.find_batch(pats), back.find_batch(pats)):
            np.testing.assert_array_equal(a, b)


class TestShardedServing:
    def _pair(self, fetch=0, cache=0):
        from repro.launch.serving import AsyncServer, ServeConfig

        s, alpha, ix, *_ = _workload("dna", 6_000, 4096)
        dev = ix.build_device(s, max_pattern_len=64)
        sh = ix.build_sharded(s, n_shards=4, max_pattern_len=64)
        rng = np.random.default_rng(11)
        pats = _pattern_mix(s, alpha, rng, sh.k_route)
        cfg = dict(pipeline=True, cache_size=cache, fetch=fetch,
                   max_wait_ms=0.0)
        ref_srv = AsyncServer(dev, ServeConfig(**cfg))
        srv = AsyncServer(sh, ServeConfig(**cfg))
        assert srv.sharded and len(srv.caches) == sh.n_shards
        # two passes: the second hits the route cache cross-batch
        ref_srv.serve(pats)
        ref = ref_srv.serve(pats)
        srv.serve(pats)
        got = srv.serve(pats)
        return ref, got, srv

    @pytest.mark.parametrize("fetch,cache", [(0, 0), (0, 256), (8, 256)])
    def test_results_identical(self, fetch, cache):
        ref, got, _ = self._pair(fetch=fetch, cache=cache)
        for i, ((rp, rw), (gp, gw)) in enumerate(zip(ref, got)):
            np.testing.assert_array_equal(rp, gp, err_msg=f"request {i}")
            if fetch:
                np.testing.assert_array_equal(rw, gw, err_msg=f"request {i}")

    def test_cache_partitions_by_shard(self):
        _, _, srv = self._pair(cache=256)
        st = srv.stats()["cache"]
        assert st["hits"] > 0
        assert len(st["per_shard"]) == srv.dev.n_shards


class TestWarmstartShardArchives:
    def test_will_load_normalizes_shard_suffix(self, tmp_path):
        from repro.launch import warmstart

        s, _, ix, *_ = _workload("dna", 6_000, 4096)
        sh = ix.build_sharded(s, n_shards=2, max_pattern_len=64)
        base = str(tmp_path / "warm_idx")
        assert not warmstart.will_load(base, sharded=True)
        assert not warmstart.will_load(base)  # base npz does not exist
        sh.save(base)
        assert warmstart.will_load(base, sharded=True)
        # the per-shard archives must NOT satisfy the unsharded check:
        # a DeviceIndex cache and a ShardedIndex cache are distinct
        assert not warmstart.will_load(base)

    def test_load_or_build_sharded_cache_hit(self, tmp_path):
        from repro.launch import warmstart

        base = str(tmp_path / "warm_idx2")
        n = 6_000

        def build(s, alphabet):
            cfg = EraConfig(memory_bytes=4096, r_bytes=512,
                            build_impl="none")
            return EraIndexer(alphabet, cfg).build_sharded(
                s, n_shards=2, max_pattern_len=64)

        first, s, _, _ = warmstart.load_or_build(
            base, "dna", n, 0, load=fabric.ShardedIndex.load, build=build,
            sharded=True)
        assert warmstart.will_load(base, sharded=True)
        builds = []
        second, s2, _, _ = warmstart.load_or_build(
            base, "dna", n, 0,
            load=fabric.ShardedIndex.load,
            build=lambda *a: builds.append(1), sharded=True)
        assert not builds  # cache hit: build never called
        # string recovery must yield the FULL string (|S| = total leaves,
        # not shard 0's slice) so the driver's workload is sampled right
        assert len(s2) == n + 1
        np.testing.assert_array_equal(np.asarray(s2), np.asarray(s))
        assert second.n_shards == first.n_shards
        rng = np.random.default_rng(13)
        pats = _pattern_mix(s, None, rng, first.k_route)[:8]
        for a, b in zip(first.find_batch(pats), second.find_batch(pats)):
            np.testing.assert_array_equal(a, b)


class TestTraceShardPids:
    def test_shard_spans_get_shard_pid(self):
        from repro.obs.trace import Tracer, validate_chrome_trace

        tr = Tracer(enabled=True)
        with tr.span("fabric/find_batch", shard=2, rows=4):
            pass
        with tr.span("serve/pad_pack", rows=8):
            pass
        chrome = tr.to_chrome()
        assert validate_chrome_trace(chrome) == []
        events = chrome["traceEvents"]
        names = {e["args"].get("name") for e in events if e["ph"] == "M"}
        assert "repro-era shard 2" in names
        shard_evt = next(e for e in events
                         if e["name"] == "fabric/find_batch")
        host_evt = next(e for e in events if e["name"] == "serve/pad_pack")
        assert shard_evt["pid"] == 2
        assert host_evt["pid"] == os.getpid()


class TestMetricsEndpoint:
    def test_serves_prometheus_text(self):
        import urllib.error
        import urllib.request

        from repro import obs
        from repro.launch.serving import start_metrics_server

        registry = obs.metrics()
        server = start_metrics_server(0)
        try:
            port = server.server_address[1]
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
            # the endpoint serves the live registry verbatim — empty when
            # REPRO_METRICS is off, the full exposition text when on
            assert body == registry.to_prometheus()
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/nope", timeout=5)
        finally:
            server.shutdown()


def test_route_depth_helper():
    assert route_depth(4, 512, 1 << 18) == 9   # 4^9 = 2^18
    assert route_depth(4, 3, 1 << 18) == 3     # capped by max_plen
    assert route_depth(256, 512, 1 << 18) == 2

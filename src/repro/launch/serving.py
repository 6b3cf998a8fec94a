"""Async continuous-batching serving stack (the ROADMAP's serving tier).

:mod:`repro.launch.query_serve` measures the *engine* — pre-padded batches
through ``find_batch_ranges``, one at a time, blocking on every call.  A
real serving front-end sees a stream of individual variable-length
requests and must turn them into sustained qps at bounded tail latency.
This module adds that tier on top of :class:`repro.core.query.DeviceIndex`:

* **Admission queue + continuous batch coalescing** — incoming requests
  queue up (bounded depth, rejects counted) and the server drains up to
  ``max_batch`` of them into the next padded batch.  Pad width and batch
  rows are bucketed to powers of two (``DeviceIndex.pad_batch`` with
  pinned ``m_pad``/``b_pad``), so the jit cache sees a handful of shapes
  instead of one per arrival mix.
* **Overlapped host/device pipeline** — JAX dispatch is asynchronous: the
  server pads/packs and ``jax.device_put``-dispatches batch *k+1* while
  batch *k*'s search is still executing, and only materializes (blocks
  on) a batch's device results one dispatch later.  The hot path never
  calls ``block_until_ready``; ``np.asarray`` at consume time is the only
  synchronization.  ``pipeline=False`` degrades to the synchronous
  one-batch-at-a-time baseline the benchmark compares against.
* **Hot-prefix route cache** — a :class:`repro.core.query.RouteCache`
  keyed on the dense top-trie route (:meth:`DeviceIndex.route_key`)
  resolves repeated hot patterns at admission, before they cost a batch
  row; hits skip the whole binary-search descent.  Exact-pattern keys
  make cache-on results byte-identical to cache-off.
* **Sharded backend** — hand the server a
  :class:`repro.core.fabric.ShardedIndex` and each admitted batch splits
  by route key into per-shard sub-batches (own pow2 pad/pack, own
  RouteCache, dispatched next to each shard's arrays); results merge
  bit-identical to the single-index path.  ``--shards`` turns it on;
  ``--metrics-port`` additionally exposes the live registry as a
  pull-based Prometheus endpoint (:func:`start_metrics_server`).

Config knobs follow the env-var GlobalConfig idiom the kernel selection
already uses (``REPRO_KERNELS``): every :class:`ServeConfig` field reads a
``REPRO_SERVE_*`` variable as its default, so drivers and CI legs can
retune the server without plumbing flags.

CPU example:
  PYTHONPATH=src python -m repro.launch.serving --dataset dna \
      --n 100000 --requests 4096 --mode all
"""

from __future__ import annotations

import argparse
import collections
import os
import threading
import time

import jax
import numpy as np

from repro import obs
from repro.core.api import EraConfig, EraIndexer
from repro.core.query import DeviceIndex, RouteCache
from repro.launch.warmstart import load_or_build


class ServeConfig:
    """Serving knobs; each field defaults from a ``REPRO_SERVE_*`` env var
    (the GlobalConfig idiom), keyword overrides win.

    * ``queue_depth``  — admission queue capacity; arrivals past it are
      rejected (counted, not raised) [REPRO_SERVE_QUEUE_DEPTH=1024]
    * ``max_batch``    — most requests coalesced into one padded batch
      [REPRO_SERVE_MAX_BATCH=256]
    * ``max_wait_ms``  — per-request batch aging: a non-full batch is
      held open for more arrivals until the OLDEST queued request has
      waited this long, then dispatches regardless of fill (closed-loop
      drivers keep the queue full, so this only matters under trickle
      load) [REPRO_SERVE_MAX_WAIT_MS=1.0]
    * ``cache_size``   — hot-prefix route cache entries, 0 disables
      [REPRO_SERVE_CACHE=4096]
    * ``fetch``        — text-window symbols returned per match via the
      fused probe+gather kernel; 0 = ranges only [REPRO_SERVE_FETCH=0]
    * ``pipeline``     — overlap dispatch of batch k+1 with consumption
      of batch k; 0 = synchronous baseline [REPRO_SERVE_PIPELINE=1]
    """

    def __init__(self, **overrides):
        env = os.environ.get
        self.queue_depth = int(env("REPRO_SERVE_QUEUE_DEPTH", "1024"))
        self.max_batch = int(env("REPRO_SERVE_MAX_BATCH", "256"))
        self.max_wait_ms = float(env("REPRO_SERVE_MAX_WAIT_MS", "1.0"))
        self.cache_size = int(env("REPRO_SERVE_CACHE", "4096"))
        self.fetch = int(env("REPRO_SERVE_FETCH", "0"))
        self.pipeline = bool(int(env("REPRO_SERVE_PIPELINE", "1")))
        for key, val in overrides.items():
            if not hasattr(self, key):
                raise TypeError(f"unknown ServeConfig field {key!r}")
            setattr(self, key, val)
        if self.queue_depth < 1 or self.max_batch < 1:
            raise ValueError("queue_depth and max_batch must be >= 1")
        if self.fetch and (self.fetch % 4 or self.fetch < 0):
            raise ValueError(f"fetch={self.fetch} must be 0 or a positive "
                             "multiple of 4")


class _Request:
    __slots__ = ("rid", "pattern", "pat_max", "t_admit")

    def __init__(self, rid, pattern, t_admit):
        self.rid = rid
        self.pattern = np.asarray(pattern, np.int32)
        self.pat_max = int(self.pattern.max(initial=0))
        self.t_admit = t_admit


class _InFlight:
    """One dispatched batch: device result handles + the bookkeeping to
    scatter them back to requests at consume time."""

    __slots__ = ("requests", "keys", "row_of", "handles", "n_rows")

    def __init__(self, requests, keys, row_of, handles, n_rows):
        self.requests = requests
        self.keys = keys
        self.row_of = row_of         # per-request batch row; None = cache hit
        self.handles = handles       # device arrays (NOT blocked on yet)
        self.n_rows = n_rows         # real rows before b_pad padding


class AsyncServer:
    """Continuous-batching server over a :class:`DeviceIndex`.

    Single-threaded event loop: ``submit`` admits requests; ``pump`` (or
    the :meth:`serve` convenience loop) coalesces a batch, dispatches it
    async, and consumes the PREVIOUS batch's results while the new one
    runs on device.  Results per request: ``(positions, window)`` —
    sorted int64 occurrence positions, plus the (fetch,) int32 text
    window at the first SA-order match when ``config.fetch`` > 0 (else
    ``None``).
    """

    def __init__(self, dev: DeviceIndex, config: ServeConfig | None = None):
        self.dev = dev
        self.config = config or ServeConfig()
        # a ShardedIndex (repro.core.fabric) swaps in the sharded backend:
        # each admitted batch splits by route key and every shard keeps
        # its own pow2-bucketed pad/pack and RouteCache (duck-typed so the
        # DeviceIndex path never imports the fabric)
        self.sharded = hasattr(dev, "shards") and hasattr(dev, "shard_span")
        n_caches = len(dev.shards) if self.sharded else 1
        self.caches = [RouteCache(self.config.cache_size)
                       for _ in range(n_caches)]
        self.cache = self.caches[0]
        self.queue: collections.deque[_Request] = collections.deque()
        self.inflight: _InFlight | None = None
        self.results: dict[int, tuple] = {}
        self.latency_s: list[float] = []
        self.n_admitted = 0
        self.n_rejected = 0
        self.n_batches = 0
        self.n_rows_padded = 0
        self.shapes: set[tuple[int, int]] = set()
        self.n_index_swaps = 0
        # span-link plumbing: each taken batch gets a fresh link id that is
        # stamped on BOTH its serve/queue_wait span and the device_dispatch
        # span(s) it becomes, so a trace viewer (and trace_smoke) can join
        # the admission-side wait to the device-side work it fed
        self._link_seq = 0
        self._cur_link = 0
        cap = dev.max_pattern_len - dev.max_pattern_len % 4
        self._width_cap = max(4, cap)
        self._bind_obs()

    def _bind_obs(self) -> None:
        """Bind tracer + registry instruments ONCE at construction: the
        per-batch hot path then costs an attribute access and (when obs
        is off) a no-op method call — the documented overhead budget."""
        tr, m = obs.tracer(), obs.metrics()
        self._tr = tr
        self._trace_on = tr.enabled
        self._metrics_on = m.enabled
        self._m_requests = m.counter(
            "serve_requests_total", "requests admitted")
        self._m_rejected = m.counter(
            "serve_rejected_total", "requests rejected at admission")
        self._m_batches = m.counter(
            "serve_batches_total", "padded batches dispatched")
        self._m_rows_real = m.counter(
            "serve_rows_real_total", "real (non-padding) batch rows")
        self._m_rows_padded = m.counter(
            "serve_rows_padded_total", "batch rows incl. pow2 padding")
        self._m_cache_hits = m.counter(
            "serve_cache_hits_total", "route-cache hits at admission")
        self._m_cache_misses = m.counter(
            "serve_cache_misses_total", "route-cache misses at admission")
        self._m_index_swaps = m.counter(
            "serve_index_swaps_total", "live index generation swaps")
        self._m_cache_flushes = m.counter(
            "serve_cache_flushes_total",
            "route-cache flushes forced by an index epoch change")
        self._h_queue_depth = m.histogram(
            "serve_queue_depth",
            buckets=obs.pow2_buckets(1, self.config.queue_depth),
            help="admission-queue depth sampled at each pump")
        self._h_batch_fill = m.histogram(
            "serve_batch_fill", buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0),
            help="real rows / padded rows per dispatched batch")
        self._h_queue_wait = m.histogram(
            "serve_queue_wait_ms",
            help="per-request wait from admission to batch dispatch")
        self._h_batch_age = m.histogram(
            "serve_batch_age_ms",
            help="oldest queued request's age at dispatch (the "
                 "max_wait_ms batch-aging signal)")
        # callback gauges read live server state at snapshot time; on
        # re-registration the newest server's callbacks win
        m.gauge("serve_cache_size",
                fn=lambda: sum(len(c) for c in self.caches),
                help="route-cache entries (all shards)")
        m.gauge("serve_cache_hit_rate", fn=lambda: self._cache_hit_rate(),
                help="route-cache lifetime hit rate (all shards)")
        m.gauge("serve_queue_depth_now", fn=lambda: len(self.queue),
                help="admission-queue depth right now")

    # ---- admission --------------------------------------------------------

    def submit(self, rid, pattern, now: float | None = None) -> bool:
        """Admit one request; False (and a counter) when the queue is full."""
        if len(self.queue) >= self.config.queue_depth:
            self.n_rejected += 1
            self._m_rejected.inc()
            return False
        self.queue.append(_Request(rid, pattern,
                                   time.perf_counter() if now is None else now))
        self.n_admitted += 1
        self._m_requests.inc()
        return True

    # ---- batching ---------------------------------------------------------

    def _bucket_width(self, m_nat: int) -> int:
        w = 4
        while w < m_nat:
            w *= 2
        return min(w, self._width_cap)

    def _bucket_rows(self, b: int) -> int:
        r = 1
        while r < b:
            r *= 2
        return min(r, self.config.max_batch)

    def _cache_hit_rate(self) -> float:
        hits = sum(c.hits for c in self.caches)
        total = hits + sum(c.misses for c in self.caches)
        return hits / total if total else 0.0

    def _cache_stats(self) -> dict:
        if not self.sharded:
            return self.cache.stats()
        agg = {"size": sum(len(c) for c in self.caches),
               "capacity": sum(c.capacity for c in self.caches),
               "hits": sum(c.hits for c in self.caches),
               "misses": sum(c.misses for c in self.caches),
               "evictions": sum(c.evictions for c in self.caches),
               "hit_rate": self._cache_hit_rate()}
        agg["per_shard"] = [c.stats() for c in self.caches]
        return agg

    def _take_batch(self) -> list[_Request] | None:
        """Pop up to ``max_batch`` requests, honoring batch aging: a
        non-full batch is held open — returns None — until the OLDEST
        queued request has waited ``max_wait_ms``, so trickle load
        coalesces without unbounded per-request staleness."""
        if not self.queue:
            return None
        cfg = self.config
        now = time.perf_counter()
        oldest_age_ms = (now - self.queue[0].t_admit) * 1e3
        if len(self.queue) < cfg.max_batch and oldest_age_ms < cfg.max_wait_ms:
            return None
        requests = [self.queue.popleft()
                    for _ in range(min(len(self.queue), cfg.max_batch))]
        self._link_seq += 1
        self._cur_link = self._link_seq
        if self._metrics_on:
            self._h_batch_age.observe(oldest_age_ms)
            for r in requests:
                self._h_queue_wait.observe((now - r.t_admit) * 1e3)
        if self._trace_on:
            self._tr.complete("serve/queue_wait",
                              int(requests[0].t_admit * 1e9),
                              int(oldest_age_ms * 1e6),
                              rows=len(requests), link=self._cur_link)
        return requests

    def _dispatch(self) -> _InFlight | None:
        """Coalesce up to ``max_batch`` queued requests into one padded
        batch and dispatch it WITHOUT blocking.  Cache hits resolve here
        (no batch row); duplicate in-batch patterns share one row."""
        requests = self._take_batch()
        if requests is None:
            return None
        if self.sharded:
            return self._dispatch_sharded(requests)
        cfg = self.config
        # with the cache OFF this is the honest one-row-per-request
        # baseline (what query_serve does); the cache brings both the
        # cross-batch memo AND in-batch dedup of repeated hot patterns
        caching = cfg.cache_size > 0
        row_of: list[int | None] = []
        key_row: dict[tuple, int] = {}
        miss_req: list[_Request] = []
        hit_vals: dict[tuple, tuple] = {}
        with self._tr.span("serve/route", rows=len(requests)):
            keys = [self.dev.route_key(r.pattern) for r in requests]
            for req, key in zip(requests, keys):
                if caching:
                    if key in hit_vals:
                        row_of.append(None)
                        continue
                    if key in key_row:
                        row_of.append(key_row[key])
                        continue
                    val = self.cache.get(key)
                    if val is not None:
                        self._m_cache_hits.inc()
                        hit_vals[key] = val
                        row_of.append(None)
                        continue
                    self._m_cache_misses.inc()
                    key_row[key] = len(miss_req)
                row_of.append(len(miss_req))
                miss_req.append(req)

        handles = (hit_vals,)
        n_rows = len(miss_req)
        if miss_req:
            pats = [r.pattern for r in miss_req]
            lens = [len(p) for p in pats]
            m_pad = self._bucket_width(-(-max(lens) // 4) * 4)
            b_pad = self._bucket_rows(n_rows)
            with self._tr.span("serve/pad_pack", rows=n_rows, b_pad=b_pad,
                               m_pad=m_pad):
                padded, lengths, route = self.dev.pad_batch(
                    pats, m_pad=m_pad, b_pad=b_pad)
                self.shapes.add((m_pad, b_pad))
                self.n_rows_padded += b_pad
                # host->device explicitly async, then dispatch; nothing
                # below blocks — the device chews on this batch while the
                # host consumes the previous one and pads the next
                padded = jax.device_put(padded)
                lengths = jax.device_put(lengths)
                route = jax.device_put(route)
            self._m_rows_real.inc(n_rows)
            self._m_rows_padded.inc(b_pad)
            self._h_batch_fill.observe(n_rows / b_pad)
            pat_max = max(r.pat_max for r in miss_req)
            with self._tr.span("serve/device_dispatch", rows=n_rows,
                               b_pad=b_pad, m_pad=m_pad,
                               fetch=cfg.fetch, link=self._cur_link):
                if cfg.fetch:
                    start, count, win, _ = self.dev.find_fetch_ranges(
                        padded, lengths, route, fetch=cfg.fetch,
                        pat_max=pat_max)
                    handles = (hit_vals, start, count, win)
                else:
                    start, count = self.dev.find_batch_ranges(
                        padded, lengths, route, pat_max=pat_max)
                    handles = (hit_vals, start, count)
        self.n_batches += 1
        self._m_batches.inc()
        return _InFlight(requests, keys, row_of, handles, n_rows)

    def _dispatch_sharded(self, requests: list[_Request]) -> _InFlight:
        """The ShardedIndex backend: split the batch by route key, then
        pad/pack and dispatch one pow2-bucketed sub-batch PER SHARD (each
        placed next to its shard's arrays).  Patterns shorter than
        ``k_route`` may span shards; they take one row in every covered
        shard and merge at consume time.  Cache lookups go to the primary
        (lowest covered) shard's RouteCache — route→shard is
        deterministic, so the per-shard caches partition the key space."""
        cfg = self.config
        caching = cfg.cache_size > 0
        # per request: None = cache hit, else [(shard, local row), ...]
        row_of: list[list | None] = []
        key_rows: dict[tuple, list] = {}
        hit_vals: dict[tuple, tuple] = {}
        shard_req: dict[int, list[_Request]] = {}
        with self._tr.span("serve/route", rows=len(requests)):
            keys = [self.dev.route_key(r.pattern) for r in requests]
            for req, key in zip(requests, keys):
                if caching:
                    if key in hit_vals:
                        row_of.append(None)
                        continue
                    if key in key_rows:  # in-batch duplicate: share rows
                        row_of.append(key_rows[key])
                        continue
                lo, hi = self.dev.shard_span(req.pattern)
                if caching:
                    val = self.caches[lo].get(key)
                    if val is not None:
                        self._m_cache_hits.inc()
                        hit_vals[key] = val
                        row_of.append(None)
                        continue
                    self._m_cache_misses.inc()
                rows = []
                for k in range(lo, hi + 1):
                    local = shard_req.setdefault(k, [])
                    rows.append((k, len(local)))
                    local.append(req)
                if caching:
                    key_rows[key] = rows
                row_of.append(rows)

        # shard k -> (real rows, start, count, win) device handles
        shard_handles: dict[int, tuple] = {}
        n_rows = 0
        for k, reqs in sorted(shard_req.items()):
            dev = self.dev.shards[k]
            pats = [r.pattern for r in reqs]
            m_pad = self._bucket_width(-(-max(len(p) for p in pats) // 4) * 4)
            b_pad = self._bucket_rows(len(reqs))
            with self._tr.span("serve/pad_pack", shard=k, rows=len(reqs),
                               b_pad=b_pad, m_pad=m_pad):
                padded, lengths, route = dev.pad_batch(
                    pats, m_pad=m_pad, b_pad=b_pad)
                self.shapes.add((m_pad, b_pad))
                self.n_rows_padded += b_pad
                target = next(iter(dev.ell.devices()))
                padded = jax.device_put(padded, target)
                lengths = jax.device_put(lengths, target)
                route = jax.device_put(route, target)
            self._m_rows_real.inc(len(reqs))
            self._m_rows_padded.inc(b_pad)
            self._h_batch_fill.observe(len(reqs) / b_pad)
            pat_max = max(r.pat_max for r in reqs)
            with self._tr.span("serve/device_dispatch", shard=k,
                               rows=len(reqs), b_pad=b_pad, m_pad=m_pad,
                               fetch=cfg.fetch, link=self._cur_link):
                if cfg.fetch:
                    start, count, win, _ = dev.find_fetch_ranges(
                        padded, lengths, route, fetch=cfg.fetch,
                        pat_max=pat_max)
                else:
                    start, count = dev.find_batch_ranges(
                        padded, lengths, route, pat_max=pat_max)
                    win = None
                shard_handles[k] = (len(reqs), start, count, win)
            n_rows += len(reqs)
        self.n_batches += 1
        self._m_batches.inc()
        return _InFlight(requests, keys, row_of, (hit_vals, shard_handles),
                         n_rows)

    def _consume(self, flight: _InFlight) -> None:
        """Materialize one batch's device results (the only blocking point)
        and scatter them back to requests; misses populate the cache."""
        if self.sharded:
            return self._consume_sharded(flight)
        cfg = self.config
        hit_vals = flight.handles[0]
        ell = self.dev.ell_host
        if flight.n_rows:
            with self._tr.span("serve/consume_sync", rows=flight.n_rows):
                start = np.asarray(flight.handles[1])[: flight.n_rows]
                count = np.asarray(flight.handles[2])[: flight.n_rows]
                win = (np.asarray(flight.handles[3])[: flight.n_rows]
                       if cfg.fetch else None)
        done: dict[int, tuple] = {}
        caching = cfg.cache_size > 0
        now = time.perf_counter()
        with self._tr.span("serve/scatter", rows=len(flight.requests)):
            for req, key, row in zip(flight.requests, flight.keys,
                                     flight.row_of):
                if row is None:
                    val = hit_vals[key]
                elif row in done:  # in-batch duplicate of a shared row
                    val = done[row]
                else:
                    s, c = int(start[row]), int(count[row])
                    # cache the MATERIALIZED response: hot repeats skip
                    # the ell slice + sort, not just the device search
                    val = (np.sort(ell[s : s + c].astype(np.int64)),
                           win[row].copy() if cfg.fetch else None)
                    done[row] = val
                    if caching:
                        self.cache.put(key, val)
                self.results[req.rid] = val
                self.latency_s.append(now - req.t_admit)

    def _consume_sharded(self, flight: _InFlight) -> None:
        """Materialize every shard's sub-batch and merge per request:
        positions concatenate and sort (shards own disjoint leaf ranges,
        so the merge is associative and bit-identical to the unsharded
        engine); the fetch window comes from the first route-ordered
        shard with a hit — the same rule as
        :meth:`repro.core.fabric.ShardedIndex.find_fetch_batch`."""
        cfg = self.config
        hit_vals, shard_handles = flight.handles
        mats: dict[int, tuple] = {}
        for k, (n_k, start, count, win) in sorted(shard_handles.items()):
            with self._tr.span("serve/consume_sync", shard=k, rows=n_k):
                mats[k] = (np.asarray(start)[:n_k], np.asarray(count)[:n_k],
                           np.asarray(win)[:n_k] if cfg.fetch else None)
        done: dict[tuple, tuple] = {}
        caching = cfg.cache_size > 0
        now = time.perf_counter()
        with self._tr.span("serve/scatter", rows=len(flight.requests)):
            for req, key, rows in zip(flight.requests, flight.keys,
                                      flight.row_of):
                if rows is None:
                    val = hit_vals[key]
                elif tuple(rows) in done:
                    val = done[tuple(rows)]
                else:
                    parts, win_out = [], None
                    for k, row in rows:
                        start, count, win = mats[k]
                        s, c = int(start[row]), int(count[row])
                        if c:
                            ell = self.dev.shards[k].ell_host
                            parts.append(ell[s : s + c].astype(np.int64))
                            if cfg.fetch and win_out is None:
                                win_out = win[row].copy()
                    if cfg.fetch and win_out is None:
                        win_out = np.full(cfg.fetch, -1, np.int32)
                    pos = (np.sort(np.concatenate(parts)) if parts
                           else np.empty(0, np.int64))
                    val = (pos, win_out if cfg.fetch else None)
                    done[tuple(rows)] = val
                    if caching:
                        self.caches[rows[0][0]].put(key, val)
                self.results[req.rid] = val
                self.latency_s.append(now - req.t_admit)

    # ---- live index swap --------------------------------------------------

    def update_index(self, dev) -> dict:
        """Swap in a new index generation (e.g. the output of
        ``EraIndexer.append_device``) without dropping queued requests.

        The in-flight batch was dispatched against the OLD index, so it is
        consumed first — its device handles and row bookkeeping are only
        meaningful there; queued-but-undispatched requests simply ride
        into the next batch against the new index.  RouteCaches memoize
        materialized positions, which an append invalidates wholesale, so
        they are flushed whenever the index ``epoch`` changes (and rebuilt
        when the shard count changes); a same-epoch swap — a replica of
        the identical index, e.g. after re-placement — keeps them warm.
        """
        if self.inflight is not None:
            self._consume(self.inflight)
            self.inflight = None
        old_epoch = int(getattr(self.dev, "epoch", 0))
        new_epoch = int(getattr(dev, "epoch", 0))
        self.dev = dev
        self.sharded = hasattr(dev, "shards") and hasattr(dev, "shard_span")
        n_caches = len(dev.shards) if self.sharded else 1
        flushed = False
        if len(self.caches) != n_caches:
            self.caches = [RouteCache(self.config.cache_size)
                           for _ in range(n_caches)]
            flushed = True
        elif new_epoch != old_epoch:
            for c in self.caches:
                c.clear()
            flushed = True
        self.cache = self.caches[0]
        cap = dev.max_pattern_len - dev.max_pattern_len % 4
        self._width_cap = max(4, cap)
        self.n_index_swaps += 1
        self._m_index_swaps.inc()
        if flushed:
            self._m_cache_flushes.inc()
        if self._trace_on:
            self._tr.instant("serve/index_swap", epoch=new_epoch,
                             flushed=int(flushed), shards=n_caches)
        return {"epoch": new_epoch, "flushed": flushed, "shards": n_caches}

    # ---- the serving loop -------------------------------------------------

    def pump(self) -> bool:
        """One loop turn: dispatch the next batch, then consume the
        previous one (which overlapped with this dispatch).  Returns
        whether anything happened — False means the loop is idle (empty,
        or holding a partial batch open for aging)."""
        if self.queue:
            self._h_queue_depth.observe(len(self.queue))
        nxt = self._dispatch()
        did = nxt is not None
        if self.inflight is not None:
            self._consume(self.inflight)
            did = True
        self.inflight = nxt
        if nxt is not None and not self.config.pipeline:
            self._consume(nxt)
            self.inflight = None
        return did

    def drain(self) -> None:
        """Run the loop until queue and pipeline are empty."""
        while self.queue or self.inflight is not None:
            if not self.pump():
                time.sleep(50e-6)  # holding a partial batch for aging

    def serve(self, patterns) -> list[tuple]:
        """Closed-loop convenience: admit ``patterns`` as fast as the queue
        allows, pump until done, return results aligned with the input."""
        base = self.n_admitted + self.n_rejected
        i = 0
        while i < len(patterns) or self.queue or self.inflight is not None:
            while i < len(patterns) and self.submit(base + i, patterns[i]):
                i += 1
            if not self.pump() and i >= len(patterns):
                time.sleep(50e-6)  # only aging can unblock now
        return [self.results.pop(base + j) for j in range(len(patterns))]

    def stats(self) -> dict:
        lat = np.asarray(self.latency_s) if self.latency_s else np.zeros(1)
        return {
            "admitted": self.n_admitted,
            "rejected": self.n_rejected,
            "served": len(self.latency_s),
            "batches": self.n_batches,
            "rows_padded": self.n_rows_padded,
            "shapes": sorted(self.shapes),
            "lat_p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
            "lat_p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
            "cache": self._cache_stats(),
        }


def start_metrics_server(port: int, host: str = "127.0.0.1"):
    """A pull-based metrics endpoint on a stdlib ``http.server`` daemon
    thread: GET ``/`` or ``/metrics`` returns the live registry in the
    Prometheus text exposition format (the same payload
    ``obs.export_all`` writes to ``era_metrics.prom``), so a scraper can
    poll a long-lived serving process instead of waiting for the exit
    snapshot.  ``port=0`` binds an ephemeral port (tests); the bound port
    is ``server.server_address[1]``.  Returns the server — call
    ``shutdown()`` to stop it; off unless a driver opts in
    (``--metrics-port``)."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path.split("?", 1)[0].rstrip("/") not in ("", "/metrics"):
                self.send_error(404)
                return
            body = obs.metrics().to_prometheus().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):  # keep the serving loop's stdout clean
            pass

    server = ThreadingHTTPServer((host, port), Handler)
    thread = threading.Thread(target=server.serve_forever,
                              name="era-metrics", daemon=True)
    thread.start()
    return server


def make_hot_workload(s: np.ndarray, rng: np.random.Generator, *,
                      n_requests: int, hot_pool: int = 32,
                      hot_frac: float = 0.8, min_len: int = 4,
                      max_len: int = 24, n_symbols: int = 4,
                      ) -> list[np.ndarray]:
    """A skewed request stream: ``hot_frac`` of requests re-ask one of
    ``hot_pool`` planted patterns (the cacheable head of the
    distribution); the rest are fresh planted-or-random patterns."""
    hot = []
    for _ in range(hot_pool):
        m = int(rng.integers(min_len, max_len + 1))
        i = int(rng.integers(0, len(s) - 1 - m))
        hot.append(np.asarray(s[i : i + m], np.int32))
    out = []
    for _ in range(n_requests):
        if rng.random() < hot_frac:
            out.append(hot[int(rng.integers(0, hot_pool))])
        else:
            m = int(rng.integers(min_len, max_len + 1))
            if rng.random() < 0.5:
                i = int(rng.integers(0, len(s) - 1 - m))
                out.append(np.asarray(s[i : i + m], np.int32))
            else:
                out.append(rng.integers(0, n_symbols, size=m,
                                        dtype=np.int32))
    return out


def run_closed_loop(dev: DeviceIndex, patterns, config: ServeConfig,
                    ) -> tuple[list[tuple], dict]:
    """Serve a whole workload closed-loop; returns (results, stats) with
    wall-clock qps added.  Warm up the jit cache first (one call per
    bucketed shape) so the measurement is steady-state serving."""
    server = AsyncServer(dev, config)
    t0 = time.perf_counter()
    results = server.serve(patterns)
    wall = time.perf_counter() - t0
    stats = server.stats()
    stats["wall_s"] = round(wall, 4)
    stats["qps"] = round(len(patterns) / max(wall, 1e-9), 1)
    return results, stats


def serve_stream(dataset_name: str = "dna", *, n: int = 100_000,
                 requests: int = 4096, hot_frac: float = 0.8,
                 hot_pool: int = 32, min_len: int = 4, max_len: int = 24,
                 memory_bytes: int = 1 << 20, seed: int = 0,
                 index_path: str | None = None, mode: str = "all",
                 shards: int = 0):
    """Build/load an index, run the serving stack, report stats per mode.

    Modes: ``sync`` (pipeline off, cache off — the one-batch-at-a-time
    baseline), ``async`` (pipeline on, cache off), ``cached`` (pipeline
    on, cache on), or ``all``.  ``shards`` > 0 serves a
    :class:`repro.core.fabric.ShardedIndex` with that many route-key
    shards (0 = the single DeviceIndex path).
    """
    max_len4 = -(-max_len // 4) * 4

    if shards > 0:
        from repro.core.fabric import ShardedIndex

        def build(s, alphabet):
            cfg = EraConfig(memory_bytes=memory_bytes, build_impl="none")
            return EraIndexer(alphabet, cfg).build_sharded(
                s, n_shards=shards, max_pattern_len=max(64, max_len4))

        dev, s, alphabet, t_build = load_or_build(
            index_path, dataset_name, n, seed, load=ShardedIndex.load,
            build=build, sharded=True)
    else:
        def build(s, alphabet):
            cfg = EraConfig(memory_bytes=memory_bytes, build_impl="none")
            return EraIndexer(alphabet, cfg).build_device(
                s, max_pattern_len=max(64, max_len4))

        dev, s, alphabet, t_build = load_or_build(
            index_path, dataset_name, n, seed, load=DeviceIndex.load,
            build=build)
    rng = np.random.default_rng(seed + 7)
    pats = make_hot_workload(s, rng, n_requests=requests, hot_pool=hot_pool,
                             hot_frac=hot_frac, min_len=min_len,
                             max_len=max_len,
                             n_symbols=len(alphabet.symbols))

    modes = {
        "sync": ServeConfig(pipeline=False, cache_size=0),
        "async": ServeConfig(pipeline=True, cache_size=0),
        "cached": ServeConfig(pipeline=True),
    }
    wanted = modes if mode == "all" else {mode: modes[mode]}
    report = {"dataset": dataset_name, "n_symbols": len(s),
              "requests": requests, "t_build_s": round(t_build, 3)}
    baseline = None
    for name, cfg in wanted.items():
        # per-mode warmup: cache-hit shrinkage changes the bucketed batch
        # shapes each mode sees, so each compiles its own jit shapes ONCE
        # before the timed steady-state pass
        run_closed_loop(dev, pats, cfg)
        _, stats = run_closed_loop(dev, pats, cfg)
        if name == "sync":
            baseline = stats["qps"]
        if baseline:
            stats["vs_sync"] = round(stats["qps"] / baseline, 2)
        report[name] = stats
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="dna")
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--requests", type=int, default=4096)
    ap.add_argument("--hot-frac", type=float, default=0.8)
    ap.add_argument("--hot-pool", type=int, default=32)
    ap.add_argument("--min-len", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=24)
    ap.add_argument("--mode", default="all",
                    choices=["all", "sync", "async", "cached"])
    ap.add_argument("--index-path", default=None,
                    help="npz cache: load the flattened index if the file "
                         "exists, else build once and save it there "
                         "(per-shard _shard{k}.npz archives with --shards)")
    ap.add_argument("--shards", type=int, default=0,
                    help="serve a ShardedIndex with this many route-key "
                         "shards (0 = single DeviceIndex)")
    ap.add_argument("--metrics-port", type=int, default=0,
                    help="expose the live metrics registry as a Prometheus "
                         "text endpoint on this port (0 = off)")
    args = ap.parse_args()
    metrics_srv = None
    if args.metrics_port:
        metrics_srv = start_metrics_server(args.metrics_port)
        print(f"metrics: http://127.0.0.1:"
              f"{metrics_srv.server_address[1]}/metrics")
    report = serve_stream(args.dataset, n=args.n, requests=args.requests,
                          hot_frac=args.hot_frac, hot_pool=args.hot_pool,
                          min_len=args.min_len, max_len=args.max_len,
                          index_path=args.index_path, mode=args.mode,
                          shards=args.shards)
    for key, val in report.items():
        print(f"{key}: {val}")
    for path in obs.export_all():
        print(f"wrote {path}")
    if metrics_srv is not None:
        metrics_srv.shutdown()


if __name__ == "__main__":
    main()

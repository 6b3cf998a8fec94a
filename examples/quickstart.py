"""Quickstart: build an ERA suffix-tree index and query it.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core.alphabet import DNA
from repro.core.api import BuildReport, EraConfig, EraIndexer
from repro.core.prepare import PrepareStats
from repro.core.vertical import VerticalStats
from repro.data.strings import dataset


def main():
    # 1. a string to index (synthetic DNA with planted repeats)
    s, alphabet = dataset("dna", 50_000, seed=0)
    print(f"string: {len(s):,} symbols over Σ={alphabet.symbols!r}+'$'")

    # 2. build the index under a deliberately tight memory budget so the
    #    vertical partitioner has real work to do.  construction="batched"
    #    (the default) stacks ALL virtual trees into one (G, F) state and
    #    drives a single vmapped elastic-range loop on device, then builds
    #    every sub-tree's nodes in one vmapped Cartesian-tree call;
    #    construction="serial" is the paper-faithful per-group reference —
    #    results are identical array-for-array, batched is just faster.
    cfg = EraConfig(
        memory_bytes=64 << 10,   # 64KB "RAM" -> many virtual trees
        r_bytes=4 << 10,         # |R| elastic-range read buffer
        construction="batched",  # one elastic loop for all groups (default)
    )
    report = BuildReport(VerticalStats(), PrepareStats())
    idx = EraIndexer(alphabet, cfg).build(s, report)

    print(f"built {len(idx.subtrees)} sub-trees in {report.n_groups} virtual "
          f"trees; F_M={report.f_max}")
    print(f"  vertical: {report.t_vertical:.2f}s ({report.vertical.scans} scans)")
    print(f"  prepare : {report.t_prepare:.2f}s ({report.prepare.iterations} "
          f"elastic iterations, ranges {min(report.prepare.ranges)}–"
          f"{max(report.prepare.ranges)})")
    print(f"  build   : {report.t_build:.2f}s "
          f"({idx.n_leaves:,} leaves, {idx.n_internal:,} internal nodes)")

    # 3. query: all occurrences of a pattern
    pattern = s[1234:1244]
    hits = idx.find(pattern)
    print(f"pattern {alphabet.decode(pattern)!r}: {len(hits)} occurrences "
          f"at {hits[:8].tolist()}…")
    assert 1234 in hits

    # 4. the same query through the tree walk (paper's O(|P|) descent)
    hits2 = idx.find_walk(pattern)
    assert np.array_equal(hits, hits2)
    print("tree-walk search agrees ✓")

    # 5. batched device path: a whole list of patterns resolves with one
    #    routing gather + vectorized binary search (repro.core.query)
    batch = [s[i : i + 8] for i in (100, 2_000, 30_000)] + [pattern]
    batch_hits = idx.find_batch(batch)
    assert np.array_equal(batch_hits[-1], hits)
    print(f"batched device search agrees ✓ "
          f"({[len(h) for h in batch_hits]} hits per pattern)")

    # 5b. serving-only deployments: EraIndexer.build_device goes string ->
    #     DeviceIndex directly — the leaf arrays are gathered into suffix-
    #     array order on device, and the per-prefix numpy SubTree dict is
    #     never materialized.  Use build() (as above) when you also need
    #     the walkable per-sub-tree form (find_walk, save/load, analytics).
    dev = EraIndexer(alphabet, cfg).build_device(s)
    assert np.array_equal(dev.find_batch([pattern])[0], hits)
    print("direct string -> DeviceIndex pipeline agrees ✓")

    # 5c. dense packing (paper §6.1, generalized per alphabet): with the
    #     default EraConfig.packing="auto" the device string is stored at
    #     Alphabet.dense_bits bits per symbol whenever that is denser than
    #     bytes — 2-bit DNA (this run), 4-bit reduced-protein classes —
    #     and construction gathers, probes and analytics all read the
    #     packed words directly, repacking to identical sort keys
    #     in-register.  Results are bit-identical to packing="bytes";
    #     the index string and its HBM probe traffic shrink ~8/bits x.
    import dataclasses
    dev_bytes = EraIndexer(
        alphabet, dataclasses.replace(cfg, packing="bytes")).build_device(s)
    assert dev.packed and dev.s_bits == alphabet.dense_bits == 2
    for a, b in zip(dev.find_batch(batch), dev_bytes.find_batch(batch)):
        assert np.array_equal(a, b)
    print(f"dense-packed index agrees ✓ (string storage: "
          f"{dev.string_nbytes:,} B packed vs {dev_bytes.string_nbytes:,} B "
          f"bytes — {dev_bytes.string_nbytes / dev.string_nbytes:.1f}x smaller)")

    # 5d. word-parallel querying: on a dense index every hot comparison —
    #     the construction sort, find_batch probes, matching statistics,
    #     suffix LCP — runs on the packed uint32 words DIRECTLY (16 DNA
    #     symbols per compare; LCP = XOR + count-leading-zeros) instead
    #     of byte-expanded keys.  That is the default; the byte-key
    #     comparison path is kept as a bit-identical oracle behind
    #     REPRO_WORD_COMPARE=byte (CI re-runs the packed suite with it
    #     pinned).  Same index, both currencies, same answers:
    import os
    prev = os.environ.get("REPRO_WORD_COMPARE")
    os.environ["REPRO_WORD_COMPARE"] = "byte"
    try:
        oracle_hits = dev.find_batch(batch)
    finally:
        if prev is None:
            del os.environ["REPRO_WORD_COMPARE"]
        else:
            os.environ["REPRO_WORD_COMPARE"] = prev
    for a, b in zip(dev.find_batch(batch), oracle_hits):
        assert np.array_equal(a, b)
    print("word-compare probes agree with the byte-key oracle ✓")

    # 5e. sustained serving: repro.launch.serving turns the single-batch
    #     engine into a continuous-batching server.  Requests are admitted
    #     into a bounded queue (overflow is rejected and counted), drained
    #     into pow2-bucketed padded batches, and dispatched WITHOUT
    #     blocking — JAX's async dispatch lets the host pad/pack batch k+1
    #     while the device searches batch k; results only synchronize at
    #     consume time (np.asarray), one dispatch behind.  A hot-prefix
    #     RouteCache (keyed on the dense top-trie route + exact pattern)
    #     memoizes materialized responses so the head of a skewed query
    #     distribution skips search AND result assembly, byte-identically.
    #     ServeConfig knobs read REPRO_SERVE_* env vars (queue depth, max
    #     batch, cache size, fused-fetch width, pipeline on/off); fetch>0
    #     returns a text window per match via the fused probe+gather
    #     kernel — one launch to verify the match and fetch its context.
    #     Caveats: the pipeline only overlaps while ≥2 batches are in the
    #     system, and cache hits land one batch late (a dispatch is in
    #     flight when its predecessor's results are consumed).
    from repro.launch.serving import ServeConfig, run_closed_loop
    stream = [s[i : i + 12] for i in (100, 2_000, 100, 30_000, 100, 2_000)]
    served, stats = run_closed_loop(
        dev, stream, ServeConfig(pipeline=True, cache_size=256, max_batch=2))
    for (pos, _), p in zip(served, stream):
        assert np.array_equal(pos, idx.find(p))
    print(f"continuous-batching server agrees ✓ ({stats['batches']} batches, "
          f"cache hit rate {stats['cache']['hit_rate']:.0%})")

    # 6. analytics: the global LCP array over the flattened index unlocks
    #    substring analytics beyond exact search (repro.core.analytics)
    eng = idx.analytics()
    rep = eng.longest_repeat()
    motif = alphabet.decode(s[rep["witness"] : rep["witness"] + rep["length"]])
    print(f"longest repeated substring: {rep['length']} symbols × "
          f"{rep['count']} occurrences ({motif[:32]!r}…)")
    print(f"distinct substrings: {eng.distinct_substrings():,}")

    # matching statistics: per-position longest match of a query vs the
    # index — a planted slice matches deep, a random tail matches shallow
    rng = np.random.default_rng(1)
    query = np.concatenate([
        s[5_000:5_040],
        rng.integers(0, 4, size=40).astype(np.uint8),
    ])
    ms, witness = eng.matching_stats(query)
    assert ms[0] >= 40  # the planted slice matches at least itself
    assert 5_000 in (witness[0], *ref_positions(idx, query[:ms[0]]))
    print(f"matching statistics: planted head matches {ms[0]} symbols, "
          f"random tail averages {ms[40:].mean():.1f}")

    # 7. observability: the flight recorder (repro.obs) traces spans and
    #    counts metrics across build, kernels, and serving — OFF by
    #    default (REPRO_TRACE=1 / REPRO_METRICS=1 env knobs, or
    #    obs.configure for scripts).  Enable BEFORE constructing what you
    #    want observed: instruments bind at creation time.
    from repro import obs
    obs.configure(trace=True, metrics_on=True, clear=True)
    dev2 = EraIndexer(alphabet, cfg).build_device(s, max_pattern_len=64)
    run_closed_loop(dev2, stream,
                    ServeConfig(pipeline=True, cache_size=256, max_batch=2))
    trace_path, prom_path = obs.export_all(
        trace_path="era_trace.json", metrics_path="era_metrics.prom")
    spans = obs.tracer().events()
    hits_total = obs.metrics().counter("serve_cache_hits_total").value
    print(f"flight recorder: {len(spans)} spans -> {trace_path} "
          f"(open at https://ui.perfetto.dev or chrome://tracing)")
    print(f"metrics snapshot -> {prom_path} "
          f"(cache hits counted: {hits_total:.0f})")
    obs.configure(trace=False, metrics_on=False, clear=True)

    # 8. sharded index fabric: on a multi-device mesh (or a simulated one:
    #    XLA_FLAGS=--xla_force_host_platform_device_count=N, set BEFORE
    #    jax imports — `python -m repro.launch.shard_run` owns that for
    #    you) construction runs SPMD via shard_map: virtual-tree groups
    #    are partitioned across the mesh, the string is replicated, and a
    #    per-shard convergence mask lets each shard leave the elastic-
    #    range loop independently.  build_sharded returns a ShardedIndex:
    #    leaf arrays sharded by top-trie route key with a replicated
    #    route→shard table, so find_batch splits each batch by route and
    #    dispatches per shard.  Results are bit-identical to the single-
    #    device engine; save() writes one archive per shard
    #    ({path}_shard{k}.npz) so each host can load only its slice.
    import jax
    n_shards = min(2, jax.device_count())
    sh = EraIndexer(alphabet, cfg).build_sharded(
        s, n_shards=n_shards, max_pattern_len=64)
    for a, b in zip(sh.find_batch(batch), dev.find_batch(batch)):
        assert np.array_equal(a, b)
    print(f"sharded fabric agrees ✓ ({sh.n_shards} shard(s) over "
          f"{jax.device_count()} device(s), route depth k={sh.k_route}; "
          f"serve with: python -m repro.launch.serving --shards N, "
          f"bench with: python -m repro.launch.shard_run --mode bench)")

    # 9. out-of-core streaming + incremental append: build_stream runs the
    #    SAME elastic-range engine through a memory-budget planner
    #    (repro.core.iomodel.plan_stream) — virtual-tree groups are sliced
    #    into chunks whose PrepareState fits device_budget bytes, and a
    #    double-buffered pipeline issues chunk k+1's host→device copy
    #    while chunk k's elastic loop runs, hiding most of the copy
    #    (StreamReport.overlap_frac).  The result is bit-identical to the
    #    one-shot build.  append_device then extends a live index without
    #    a full rebuild: a terminal-tail scan + incremental re-partition
    #    finds the few affected sub-trees, only those re-run the elastic
    #    loop, and every untouched leaf segment is spliced over verbatim
    #    (AppendReport.reuse_frac).  Each append bumps DeviceIndex.epoch —
    #    persisted in save()/load() — so AsyncServer.update_index knows to
    #    flush its RouteCaches when handed the new index.
    dev_s, sr = EraIndexer(alphabet, cfg).build_stream(
        s, device_budget=64 << 10, max_pattern_len=64)
    for a, b in zip(dev_s.find_batch(batch), dev.find_batch(batch)):
        assert np.array_equal(a, b)
    print(f"streaming build agrees ✓ ({sr.n_chunks} chunks, "
          f"overlap_frac={sr.overlap_frac:.2f})")
    extra = np.random.default_rng(9).integers(
        0, alphabet.base - 1, size=500).astype(s.dtype)
    s_grown = np.concatenate([s[:-1], extra, s[-1:]])
    from repro.core.api import AppendReport
    # a tight budget means MANY small sub-trees, so the append's affected
    # set is a thin slice of the partition and most leaves carry over
    import dataclasses as _dc
    tight = EraIndexer(alphabet, _dc.replace(cfg, memory_bytes=8 << 10))
    dev_t = tight.build_device(s, max_pattern_len=64)
    arep = AppendReport()
    dev_g, _ = tight.append_device(dev_t, s_grown, arep)
    full = tight.build_device(s_grown, max_pattern_len=64)
    for a, b in zip(dev_g.find_batch(batch), full.find_batch(batch)):
        assert np.array_equal(a, b)
    print(f"incremental append agrees ✓ (rebuilt {arep.n_affected}/"
          f"{arep.n_prefixes} sub-trees, reuse_frac={arep.reuse_frac:.2f}, "
          f"epoch {dev_t.epoch}→{dev_g.epoch})")

    # 10. engine tuning knobs: every construction path above ran the
    #     PROMOTED hot-path defaults — fused single-lane sort keys (the
    #     (area, key, tie) triple packed into one uint32 lane when the
    #     bit budget fits) and tail compaction (once most rows have
    #     converged, each iteration gathers only the still-active rows
    #     into a narrow (G, f') state, steps there, and scatters back).
    #     Both are exact transforms with escape hatches for A/B runs and
    #     bisection: REPRO_SORT=lexsort and REPRO_COMPACT=off pin the
    #     reference engines (or EraConfig(sort_fuse=..., compaction=...)
    #     / the --sort / --no-compact driver flags per run); CI keeps the
    #     lexsort oracle leg green on every PR.  EraConfig(
    #     node_lcp="words") additionally rebuilds the node-build
    #     divergence rows from the packed text via the word-compare LCP
    #     kernel instead of the stored construction state — same nodes.
    #
    #     Kernel tiles (reads per grid step) come from
    #     repro.roofline.autotune: dispatch resolves each (backend,
    #     kernel, dtype-bits, n-bucket) through the autotune table the
    #     environment names (REPRO_AUTOTUNE_TABLE — written only by
    #     explicit sweeps, never at import), else the VMEM/HBM roofline
    #     model when REPRO_AUTOTUNE=model, else the static defaults.
    #     Tiles change the blocking, never results:
    from repro.roofline import autotune
    table = autotune.AutotuneTable()
    table.fill_model("cpu", {"range_gather": 64, "suffix_lcp": 256},
                     bits=alphabet.dense_bits, n=len(s))
    autotune.set_active_table(table)      # or table.save(path) + env
    dev_tuned = EraIndexer(alphabet, cfg).build_device(s)
    autotune.set_active_table(None)
    for a, b in zip(dev_tuned.find_batch(batch), dev.find_batch(batch)):
        assert np.array_equal(a, b)
    print(f"autotuned tiles agree ✓ ({len(table.entries)} table entries, "
          f"e.g. range_gather -> "
          f"{table.get('cpu', 'range_gather', alphabet.dense_bits, len(s))})")


def ref_positions(idx, pattern):
    return idx.find(np.asarray(pattern)).tolist()


if __name__ == "__main__":
    main()

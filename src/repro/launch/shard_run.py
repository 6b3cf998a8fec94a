import argparse
import json
import os
import sys


def _parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="Sharded index fabric driver: simulate an N-device "
                    "mesh on CPU, run SPMD construction, optionally "
                    "benchmark it against the single-device batched "
                    "baseline or save the per-shard archives.")
    ap.add_argument("--devices", type=int, default=4,
                    help="simulated host devices (XLA_FLAGS "
                         "--xla_force_host_platform_device_count; must be "
                         "set before jax imports, which is why this driver "
                         "exists) [4]")
    ap.add_argument("--dataset", default="dna")
    ap.add_argument("--n", type=int, default=120_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--memory-bytes", type=int, default=1 << 16)
    ap.add_argument("--shards", type=int, default=0,
                    help="index route-key shards (0 = mesh size)")
    ap.add_argument("--mode", default="build",
                    choices=["build", "bench", "save"],
                    help="build: construct + verify a ShardedIndex; "
                         "bench: time sharded vs single-device baseline; "
                         "save: build and write per-shard npz archives")
    ap.add_argument("--index-path", default=None,
                    help="archive base path for --mode save "
                         "(writes {path}_shard{k}.npz)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--sort", default=None, choices=["fused", "lexsort"],
                    help="elastic-step sort engine (REPRO_SORT): fused "
                         "single-lane keys (default) or the lexsort oracle")
    ap.add_argument("--no-compact", action="store_true",
                    help="disable tail compaction (REPRO_COMPACT=off)")
    ap.add_argument("--autotune", default=None,
                    choices=["off", "table", "model"],
                    help="kernel tile selection mode (REPRO_AUTOTUNE)")
    ap.add_argument("--autotune-table", default=None,
                    help="autotune table path (REPRO_AUTOTUNE_TABLE)")
    ap.add_argument("--json", action="store_true",
                    help="emit one machine-readable JSON object on stdout "
                         "(benchmarks/bench_fabric.py subprocess mode)")
    return ap.parse_args(argv)


def run(args) -> dict:
    """The post-import body: everything that touches jax."""
    import time

    import numpy as np

    from repro.core import fabric
    from repro.core.api import EraConfig, EraIndexer
    from repro.core.prepare import subtree_prepare_batch
    from repro.data.strings import dataset
    from repro.launch.compile_cache import use_compile_cache

    import jax

    use_compile_cache()
    s, alphabet = dataset(args.dataset, args.n, seed=args.seed)
    cfg = EraConfig(memory_bytes=args.memory_bytes, r_bytes=4096,
                    build_impl="none")
    ix = EraIndexer(alphabet, cfg)
    out = {
        "dataset": args.dataset, "n": args.n, "seed": args.seed,
        "memory_bytes": args.memory_bytes,
        "devices": jax.device_count(), "backend": jax.default_backend(),
    }

    if args.mode == "bench":
        groups = ix.partition(s)
        capacity = ix._capacity(groups)
        s_padded = ix._device_text(s)
        ecfg = cfg.elastic_config()

        def best_of(fn):
            fn()  # warmup covers every (w, f_prime) program compile
            times = []
            for _ in range(args.repeats):
                t0 = time.perf_counter()
                fn()
                times.append(time.perf_counter() - t0)
            return min(times)

        t_base = best_of(
            lambda: subtree_prepare_batch(s_padded, groups, capacity, ecfg))
        t_shard = best_of(
            lambda: fabric.sharded_prepare(s_padded, groups, capacity, ecfg))
        out.update(groups=len(groups), capacity=capacity,
                   t_baseline_s=round(t_base, 4),
                   t_sharded_s=round(t_shard, 4),
                   speedup=round(t_base / t_shard, 3))
        return out

    n_shards = args.shards or jax.device_count()
    t0 = time.perf_counter()
    sh = ix.build_sharded(s, n_shards=n_shards)
    out["t_build_s"] = round(time.perf_counter() - t0, 4)
    out["shards"] = sh.stats()
    # a probe batch proves the routed query path end to end
    rng = np.random.default_rng(args.seed + 1)
    pats = [np.asarray(s[int(i) : int(i) + 12], np.int32)
            for i in rng.integers(0, len(s) - 13, size=16)]
    hits = sh.find_batch(pats)
    out["probe_hits"] = [int(len(h)) for h in hits]
    if args.mode == "save":
        if not args.index_path:
            raise SystemExit("--mode save needs --index-path")
        sh.save(args.index_path)
        out["archives"] = fabric.ShardedIndex.shard_files(args.index_path)
    return out


def main(argv=None):
    args = _parse_args(argv)
    # engine knobs travel via the env-dispatch idiom so every layer
    # (batched step, fabric shard step, kernel tile pick) sees them
    if args.sort is not None:
        os.environ["REPRO_SORT"] = args.sort
    if args.no_compact:
        os.environ["REPRO_COMPACT"] = "off"
    if args.autotune is not None:
        os.environ["REPRO_AUTOTUNE"] = args.autotune
    if args.autotune_table is not None:
        os.environ["REPRO_AUTOTUNE_TABLE"] = args.autotune_table
    # the whole point of this driver: the simulated device count must be
    # in the environment BEFORE the first jax import (same idiom as
    # launch/dryrun.py) — so argparse runs first and jax imports inside
    # run()
    if "jax" in sys.modules:
        import jax
        if jax.device_count() < args.devices:
            raise SystemExit(
                "jax is already imported with "
                f"{jax.device_count()} device(s); shard_run must own the "
                "process (python -m repro.launch.shard_run)")
    else:
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices}").strip()
    out = run(args)
    if args.json:
        print(json.dumps(out, sort_keys=True))
    else:
        for key, val in out.items():
            print(f"{key}: {val}")


if __name__ == "__main__":
    main()

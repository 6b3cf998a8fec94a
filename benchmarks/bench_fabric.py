"""Sharded-fabric construction throughput: SPMD mesh vs single-device.

One baseline row (``subtree_prepare_batch``, the default batched engine)
and one sharded row (:func:`repro.core.fabric.sharded_prepare` over the
device mesh) at a G ≈ 100 workload, derived carrying the speedup and its
attribution.  On the CI host the mesh is SIMULATED
(``--xla_force_host_platform_device_count``) on one physical core, so any
speedup is NOT device parallelism.  The fused sort key and tail
compaction that used to be fabric-only are now the default batched
engine too (both rows run them), so the remaining delta is the fabric's
per-shard convergence mask on the tail iterations.  On a real
multi-device mesh the same program adds actual parallel speedup on top.

The suite runs in-process on the devices this process has: a one-device
process measures the sharded loop over a one-device mesh.  It never
starts a child process, which could not reach a chip this process holds.
The CI fabric job runs it under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``, a simulated mesh.
"""

from __future__ import annotations

from benchmarks.common import emit, timeit


def _bench_inprocess(n: int, memory_bytes: int, repeats: int) -> dict:
    import jax

    from repro.core import fabric
    from repro.core.api import EraConfig, EraIndexer
    from repro.core.prepare import subtree_prepare_batch
    from repro.data.strings import dataset

    s, alphabet = dataset("dna", n, seed=0)
    cfg = EraConfig(memory_bytes=memory_bytes, r_bytes=4096,
                    build_impl="none")
    ix = EraIndexer(alphabet, cfg)
    groups = ix.partition(s)
    capacity = ix._capacity(groups)
    s_padded = ix._device_text(s)
    ecfg = cfg.elastic_config()
    t_base = timeit(
        lambda: subtree_prepare_batch(s_padded, groups, capacity, ecfg),
        repeats=repeats, warmup=1)
    t_shard = timeit(
        lambda: fabric.sharded_prepare(s_padded, groups, capacity, ecfg),
        repeats=repeats, warmup=1)
    return {"devices": jax.device_count(), "groups": len(groups),
            "capacity": capacity, "t_baseline_s": t_base,
            "t_sharded_s": t_shard, "speedup": t_base / max(t_shard, 1e-9)}


def run(quick: bool = True) -> None:
    n = 120_000 if quick else 400_000
    memory_bytes = 1 << 16 if quick else 1 << 17
    repeats = 2 if quick else 3

    import jax

    res = _bench_inprocess(n, memory_bytes, repeats)

    from benchmarks.bench_build import engine_stamp

    g, cap = res.get("groups", "?"), res.get("capacity", "?")
    stamp = engine_stamp()
    emit(f"fabric/baseline/n={n}", res["t_baseline_s"],
         f"groups={g} capacity={cap} engine=batched {stamp}")
    emit(f"fabric/sharded/n={n}", res["t_sharded_s"],
         f"devices={res['devices']} groups={g} "
         f"speedup={res['speedup']:.2f}x "
         f"attribution=shard_mask {stamp} "
         f"simulated_mesh={jax.default_backend() == 'cpu'}")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()

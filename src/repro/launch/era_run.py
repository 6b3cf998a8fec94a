"""Distributed ERA construction driver.

Maps the paper's two parallel architectures (§5) onto this machine:

* **shared-memory / shared-disk** → multi-device single host: the string is
  replicated (one HBM copy per device), virtual trees are distributed by
  the fault-tolerant work queue, each device runs the elastic-range
  pipeline on its groups.  Here the workers are simulated: they take
  turns in one process, and every worker's groups run on the default
  device (device 0), on CPU and on a TPU host alike — not one chip per
  worker.  The multi-chip path is the sharded fabric
  (``EraIndexer.build_sharded``).

* **shared-nothing** → multi-pod: identical structure; the initial string
  broadcast cost (paper Table 3 excludes it; we report it) is modeled by
  the I/O layer.

The ``model`` mesh axis is idle for ERA (no matmul to TP-shard) — all 512
chips act as independent workers, giving 512-way task parallelism, which
is exactly the paper's scaling story (no merge phase).

``era_prepare_batch`` — the ``shard_map``-able batched step used by the
dry-run to prove the ERA step lowers on the production mesh — is a thin
alias for the shared batched engine in :mod:`repro.core.prepare`; the
worker pool below consumes the same engine (each worker pulls a CHUNK of
groups and runs one vmapped elastic loop over it) instead of a private
per-group loop.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.core.alphabet import ALPHABETS
from repro.core.api import BuildReport, EraConfig, EraIndexer
from repro.core.prepare import PrepareState, prepare_step_batch
from repro.core.vertical import VerticalStats
from repro.core.prepare import PrepareStats
from repro.data.strings import dataset
from repro.runtime.scheduler import WorkQueue
from repro.launch.compile_cache import use_compile_cache


# ---------------------------------------------------------------------------
# shard_map-able batched prepare step (for the dry-run / real pods)
# ---------------------------------------------------------------------------

def era_prepare_batch(s_padded, states: PrepareState, *, w: int):
    """One elastic-range iteration for a batch of virtual trees.

    states: PrepareState with leading group-batch dim (G, F).  The caller
    shard_maps / shards G over (pod, data, model) — groups are independent,
    so the only communication is the replicated string read.

    ``s_padded`` is either the terminal-padded byte string or a dense
    k-bit :class:`repro.core.packing.PackedText` (paper §6.1: 2-bit DNA —
    ``8/bits``x less replicated string HBM and gather traffic); the
    representation dispatches inside the step and results are identical.

    The implementation is the shared batched construction engine
    (:func:`repro.core.prepare.prepare_step_batch`) — the same step the
    default ``EraIndexer.build`` pipeline drives to convergence.
    """
    return prepare_step_batch(s_padded, states, w=w)


# ---------------------------------------------------------------------------
# Worker-pool construction driver (simulated workers on CPU)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class WorkerReport:
    worker: str
    groups: int = 0
    seconds: float = 0.0


def build_distributed(
    s: np.ndarray,
    alphabet,
    era_cfg: EraConfig,
    n_workers: int = 4,
    *,
    checkpoint_path: str | None = None,
    fail_worker: str | None = None,
    fail_after: int = 1,
    groups_per_pull: int = 4,
):
    """Master/worker construction with the fault-tolerant queue.

    Each worker turn pulls up to ``groups_per_pull`` virtual trees and runs
    them through the shared batched (G, F) engine
    (``EraIndexer.process_groups``) — one vmapped elastic loop per chunk,
    the same engine the single-host ``build`` uses — then completes the
    tasks individually so failure/recovery stays per-group.

    ``fail_worker`` simulates a node loss after ``fail_after`` completed
    groups (the failure-injection path used by tests): its in-flight work
    is re-queued and picked up by the survivors.
    """
    indexer = EraIndexer(alphabet, era_cfg)
    report = BuildReport(VerticalStats(), PrepareStats())
    groups = indexer.partition(s, report)
    capacity = indexer._capacity(groups)
    s_padded = indexer._device_text(s)  # dense-packed for DNA (EraConfig.packing)

    queue = WorkQueue(checkpoint_path=checkpoint_path)
    queue.add_tasks([g.total_freq for g in groups], payloads=groups)

    workers = [f"w{i}" for i in range(n_workers)]
    dead: set[str] = set()
    completed: dict[int, list] = {}
    per_worker = {w: WorkerReport(worker=w) for w in workers}
    fail_count = 0

    while not queue.drained:
        progressed = False
        for w in workers:
            if w in dead:
                continue
            tasks = []
            while len(tasks) < max(1, groups_per_pull):
                task = queue.pull(w)
                if task is None:
                    break
                tasks.append(task)
            if not tasks:
                continue
            progressed = True
            t0 = time.perf_counter()
            results = indexer.process_groups(
                s_padded, [t.payload for t in tasks], capacity)
            dt = (time.perf_counter() - t0) / len(tasks)
            for task, subtrees in zip(tasks, results):
                if w == fail_worker and fail_count >= fail_after:
                    # simulate the node dying mid-chunk: this task and the
                    # rest of the chunk stay in flight and get re-queued
                    dead.add(w)
                    queue.mark_failed(w)
                    break
                queue.complete(task.task_id, worker=w, elapsed_s=dt)
                completed[task.task_id] = subtrees
                per_worker[w].groups += 1
                per_worker[w].seconds += dt
                if w == fail_worker:
                    fail_count += 1
        if not progressed and not queue.drained:
            # everything in flight on dead workers: force requeue
            for w in list(dead):
                queue.mark_failed(w)

    from repro.core.suffix_tree import SuffixTreeIndex

    subtrees = {}
    for sts in completed.values():
        for st in sts:
            subtrees[st.prefix] = st
    idx = SuffixTreeIndex(s=np.asarray(s), alphabet=alphabet, subtrees=subtrees)
    return idx, queue.stats(), list(per_worker.values())


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="dna")
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--memory-mb", type=float, default=1.0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--batch-groups", type=int, default=4,
                    help="virtual trees per worker pull (batched engine width)")
    ap.add_argument("--stream", action="store_true",
                    help="out-of-core single-host build: double-buffered "
                         "chunk pipeline instead of the worker pool")
    ap.add_argument("--device-budget-mb", type=float, default=None,
                    help="device bytes the streaming PrepareState may "
                         "occupy (with --stream; default unbounded = one "
                         "chunk)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable the standby-buffer copy/compute overlap "
                         "(with --stream; the synchronous baseline)")
    ap.add_argument("--sort", default=None, choices=["fused", "lexsort"],
                    help="elastic-step sort engine: fused single-lane keys "
                         "(default) or the three-lane lexsort oracle")
    ap.add_argument("--no-compact", action="store_true",
                    help="disable tail compaction (sort every row even "
                         "after its group has converged)")
    ap.add_argument("--autotune", default=None,
                    choices=["off", "table", "model"],
                    help="kernel tile selection: off = static defaults, "
                         "table = on-disk autotune table (fall back to the "
                         "roofline model), model = roofline model only")
    ap.add_argument("--autotune-table", default=None,
                    help="autotune table path (REPRO_AUTOTUNE_TABLE; "
                         "no table is read unless one is named)")
    args = ap.parse_args()

    import os
    if args.autotune is not None:
        os.environ["REPRO_AUTOTUNE"] = args.autotune
    if args.autotune_table is not None:
        os.environ["REPRO_AUTOTUNE_TABLE"] = args.autotune_table

    s, alpha = dataset(args.dataset, args.n)
    cfg = EraConfig(memory_bytes=int(args.memory_mb * (1 << 20)),
                    build_impl="none",
                    sort_fuse=(None if args.sort is None
                               else args.sort == "fused"),
                    compaction=False if args.no_compact else None)
    if args.stream:
        budget = (None if args.device_budget_mb is None
                  else int(args.device_budget_mb * (1 << 20)))
        report = BuildReport(VerticalStats(), PrepareStats())
        t0 = time.perf_counter()
        dev, sr = EraIndexer(alpha, cfg).build_stream(
            s, report, device_budget=budget, overlap=not args.no_overlap)
        dt = time.perf_counter() - t0
        print(f"indexed {args.n} symbols in {dt:.2f}s streaming "
              f"({sr.n_chunks} chunks, overlap={'on' if sr.overlap else 'off'})")
        print(f"stream: groups={sr.groups} iterations={sr.iterations} "
              f"copied={sr.bytes_copied / 1e6:.1f}MB "
              f"copy={sr.copy_s * 1e3:.1f}ms "
              f"hidden={sr.copy_hidden_s * 1e3:.1f}ms "
              f"(overlap_frac={sr.overlap_frac:.2f})")
        print(f"leaves={dev.n_leaves} subtrees={dev.n_subtrees}")
        return
    t0 = time.perf_counter()
    idx, qstats, workers = build_distributed(
        s, alpha, cfg, n_workers=args.workers, checkpoint_path=args.checkpoint,
        groups_per_pull=args.batch_groups)
    dt = time.perf_counter() - t0
    print(f"indexed {args.n} symbols in {dt:.2f}s with {args.workers} workers")
    print(f"queue: {qstats}")
    for w in workers:
        print(f"  {w.worker}: {w.groups} groups, {w.seconds:.2f}s")
    print(f"leaves={idx.n_leaves} subtrees={len(idx.subtrees)}")


if __name__ == "__main__":
    main()

"""EraIndexer — the end-to-end ERA pipeline (paper §4 + §5).

vertical partitioning → grouping → elastic-range SubTreePrepare →
BuildSubTree → assembled :class:`SuffixTreeIndex`.

Two construction engines share every stage (``EraConfig.construction``):

* ``batched`` (default) — ALL virtual trees stacked into one padded (G, F)
  state, driven by a single jitted vmapped elastic-range loop with donated
  buffers (:func:`repro.core.prepare.subtree_prepare_batch`); the node sets
  of every sub-tree are then built in ONE vmapped Cartesian-tree call
  (:func:`repro.core.build.build_parallel_batch`).  This is the paper's §5
  parallelism made the real path — ``shard_map`` over G distributes it.
* ``serial`` — the paper-faithful §4 reference: one group at a time through
  :func:`repro.core.prepare.subtree_prepare`, per-prefix host builders.
  Results are identical array-for-array; tier-1 tests cross-check.

The parallel drivers (shared-memory / shared-nothing analogues) live in
:mod:`repro.launch.era_run`; workers consume the same batched engine.  The
serving-side counterpart is :meth:`EraIndexer.build_device`, which goes
string → :class:`repro.core.query.DeviceIndex` directly — the leaf arrays
are gathered into suffix-array order on device and the per-prefix numpy
``SubTree`` dict is never materialized (use :meth:`build` when you need the
walkable per-sub-tree form).
"""

from __future__ import annotations

import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import build as build_mod
from repro.core import packing
from repro.core.alphabet import Alphabet
from repro.core.prepare import (
    ElasticConfig,
    PrepareStats,
    StreamReport,
    segments_of,
    subtree_prepare,
    subtree_prepare_batch,
    subtree_prepare_stream,
)
from repro.core.suffix_tree import SubTree, SuffixTreeIndex
from repro.core.vertical import VerticalStats, vertical_partition_grouped

NODE_BYTES = 16  # sizeof(tree_node): parent + depth + witness + pad (SoA)


@dataclasses.dataclass(frozen=True)
class EraConfig:
    """Memory-budget and strategy knobs (paper §4.4 memory allocation)."""

    memory_bytes: int = 64 << 20   # total budget; 60% to the sub-tree (MTS)
    r_bytes: int = 1 << 20         # |R| read buffer (32MB DNA / 256MB protein in paper)
    w_min: int = 4
    w_max: int = 256
    elastic: bool = True
    static_w: int = 16             # used when elastic=False (Fig. 9b ablation)
    group: bool = True             # virtual trees on/off (Fig. 9a ablation)
    vertical_strategy: str = "histogram"  # or "positions" (beyond-paper)
    build_impl: str = "numpy"      # numpy | scan | parallel | none; selects the
    #                                serial engine's per-prefix builder — the
    #                                batched engine always uses the vmapped
    #                                parallel builder unless "none" (skip nodes)
    construction: str = "batched"  # batched (one (G,F) loop) | serial (per group)
    packing: str = "auto"          # device string representation (paper §6.1):
    #                                auto  — dense k-bit when the alphabet is
    #                                        denser than bytes (2-bit DNA,
    #                                        4-bit protein classes), else bytes
    #                                dense — force Alphabet.dense_bits packing
    #                                bytes — one byte per symbol (reference)
    sort_fuse: bool | None = None  # fused single-lane sort keys in the elastic
    #                                step; None = promoted default (on) unless
    #                                REPRO_SORT=lexsort pins the oracle
    compaction: bool | None = None  # tail compaction (sort only still-active
    #                                rows); None = promoted default (on) unless
    #                                REPRO_COMPACT=off pins the oracle
    node_lcp: str = "state"        # node-build divergence source:
    #                                state — stored b_off from the prepare
    #                                        state (free, the default)
    #                                words — recomputed from the text via the
    #                                        word-compare LCP (bit-identical;
    #                                        decouples the Cartesian-tree pass
    #                                        from the construction state)

    @property
    def mts_bytes(self) -> int:
        return int(0.6 * self.memory_bytes)

    @property
    def f_max(self) -> int:
        """Eq. 1: F_M = MTS / (2 * sizeof(tree_node))."""
        return max(2, self.mts_bytes // (2 * NODE_BYTES))

    @property
    def r_symbols(self) -> int:
        return self.r_bytes  # 1 byte per symbol code in this implementation

    def elastic_config(self) -> ElasticConfig:
        return ElasticConfig(
            r_budget_symbols=self.r_symbols,
            w_min=self.w_min,
            w_max=self.w_max,
            elastic=self.elastic,
            static_w=self.static_w,
        )


@dataclasses.dataclass
class BuildReport:
    vertical: VerticalStats
    prepare: PrepareStats
    n_prefixes: int = 0
    n_groups: int = 0
    f_max: int = 0
    t_vertical: float = 0.0
    t_prepare: float = 0.0
    t_build: float = 0.0

    @property
    def t_total(self) -> float:
        return self.t_vertical + self.t_prepare + self.t_build


@dataclasses.dataclass
class AppendReport:
    """Accounting for one incremental append (build only the affected
    sub-trees, reuse every untouched leaf segment)."""

    n_old: int = 0             # |S_old| real symbols
    n_new: int = 0             # |S_new| real symbols
    b_star: int = 0            # start of the terminal-affected suffix tail
    n_prefixes: int = 0        # sub-trees in the merged index
    n_affected: int = 0        # sub-trees rebuilt
    leaves_rebuilt: int = 0
    leaves_reused: int = 0
    t_scan: float = 0.0        # terminal-affected boundary scan (queries)
    partition_fallback: bool = False  # delta changed the split structure
    t_partition: float = 0.0
    t_prepare: float = 0.0     # elastic-range loop over affected groups
    t_merge: float = 0.0

    @property
    def t_total(self) -> float:
        return self.t_scan + self.t_partition + self.t_prepare + self.t_merge

    @property
    def reuse_frac(self) -> float:
        total = self.leaves_rebuilt + self.leaves_reused
        return self.leaves_reused / total if total else 0.0


def _terminal_affected_start(count_fn, s_new: np.ndarray, n_old_real: int,
                             max_plen: int, batch: int = 64) -> int:
    """First position ``b*`` of the terminal-affected suffix tail.

    Replacing the old terminal with appended symbols can only reorder a
    sub-tree if some pair of its suffixes used to diverge AT the old
    terminal — i.e. the later suffix's whole tail ``S_old[b:]`` occurs at
    least twice in ``S_old``.  That predicate is suffix-closed (if a tail
    repeats, every shorter tail repeats too), so the affected positions
    form one contiguous range ``[b*, n_old_real)`` found by a backward
    scan of count queries against the OLD index — O(log n) queries on
    random text.  Tails longer than the index's ``max_pattern_len`` are
    checked on their truncated prefix: count < 2 there proves the full
    tail unique (necessary condition), count >= 2 is treated as affected
    (conservative, never unsound).
    """
    cap = max(4, max_plen // 4 * 4)  # stays under pad_batch's width check
    b = n_old_real - 1
    while b >= 0:
        bs = list(range(b, max(b - batch, -1), -1))
        pats = [np.asarray(s_new[bb:min(n_old_real, bb + cap)],
                           np.int32) for bb in bs]
        counts = count_fn(pats)
        for bb, c in zip(bs, counts):
            if int(c) < 2:
                return bb + 1
        b -= batch
    return 0


_BUILDERS = {
    "numpy": lambda ell, b, n: build_mod.build_numpy(np.asarray(ell), np.asarray(b), n),
    "scan": lambda ell, b, n: build_mod.build_scan(jnp.asarray(ell), jnp.asarray(b), n),
    "parallel": lambda ell, b, n: build_mod.build_parallel(jnp.asarray(ell), jnp.asarray(b), n),
}


def _sorted_segments(groups):
    """(prefix, group_index, offset, freq) per sub-tree, sorted by prefix.

    Prefix-freeness makes sorted tuple order the lexicographic suffix
    order, so concatenating the leaf segments in this order yields the
    suffix array (the DeviceIndex layout).
    """
    entries = []
    for g_i, g in enumerate(groups):
        for (off, freq), p in zip(segments_of(g), g.prefixes):
            entries.append((p.symbols, g_i, off, freq))
    entries.sort(key=lambda e: e[0])
    return entries


def _entry_flat_idx(entry, f_cap: int) -> np.ndarray:
    """Indices of one sub-tree's leaf segment in the flattened (G, F) state."""
    _, g_i, off, freq = entry
    return g_i * f_cap + off + np.arange(freq, dtype=np.int64)


def _flatten_state(groups, states):
    """(prefixes, freqs, ell) in sorted prefix order from a final (G, F)
    prepare state — the shared flatten behind every index assembly path.
    Device states stay on device (one gather); the streaming engine's
    host (numpy) states flatten host-side."""
    entries = _sorted_segments(groups)
    f_cap = states.L.shape[1]
    flat_idx = np.concatenate([_entry_flat_idx(e, f_cap) for e in entries])
    if isinstance(states.L, np.ndarray):
        ell = states.L.reshape(-1)[flat_idx].astype(np.int32)
    else:
        ell = jnp.take(states.L.reshape(-1), jnp.asarray(flat_idx, jnp.int32))
    prefixes = [e[0] for e in entries]
    freqs = np.array([e[3] for e in entries], np.int32)
    return prefixes, freqs, ell


class EraIndexer:
    def __init__(self, alphabet: Alphabet, config: EraConfig = EraConfig()):
        self.alphabet = alphabet
        self.config = config
        if config.construction not in ("serial", "batched"):
            raise ValueError(
                f"unknown construction engine {config.construction!r}; "
                "choose 'serial' or 'batched'")
        if config.packing not in ("auto", "dense", "bytes"):
            raise ValueError(
                f"unknown packing mode {config.packing!r}; "
                "choose 'auto', 'dense' or 'bytes'")
        if config.build_impl not in (*_BUILDERS, "none"):
            # fail fast: the batched engine always uses the vmapped parallel
            # builder (unless "none"), so a typo would otherwise pass silently
            raise ValueError(
                f"unknown build_impl {config.build_impl!r}; "
                f"choose one of {sorted((*_BUILDERS, 'none'))}")
        if config.node_lcp not in ("state", "words"):
            raise ValueError(
                f"unknown node_lcp {config.node_lcp!r}; "
                "choose 'state' or 'words'")

    def partition(self, s: np.ndarray, report: BuildReport | None = None):
        """Vertical partitioning + grouping (the master-node phase)."""
        cfg = self.config
        vstats = report.vertical if report else VerticalStats()
        t0 = time.perf_counter()
        with obs.tracer().span("build/vertical", n=len(s),
                               f_max=cfg.f_max) as sp:
            groups = vertical_partition_grouped(
                s,
                base=self.alphabet.base,
                f_max=cfg.f_max,
                strategy=cfg.vertical_strategy,
                group=cfg.group,
                stats=vstats,
            )
            sp.set(groups=len(groups))
        if report:
            report.t_vertical = time.perf_counter() - t0
            report.n_groups = len(groups)
            report.n_prefixes = sum(len(g.prefixes) for g in groups)
            report.f_max = cfg.f_max
        return groups

    def _capacity(self, groups) -> int:
        return min(self.config.f_max,
                   max((g.total_freq for g in groups), default=2))

    def _pad(self, s: np.ndarray) -> jnp.ndarray:
        # pad so gathers past the end stay in-bounds (terminal padding)
        return jnp.asarray(self.alphabet.pad_string(s, extra=2 * self.config.w_max + 8))

    def _device_text(self, s: np.ndarray):
        """The device-resident string for construction gathers: dense
        k-bit :class:`repro.core.packing.PackedText` (paper §6.1 — the
        default for sub-byte alphabets) or the terminal-padded byte
        array, per ``EraConfig.packing``.  Construction output is
        bit-identical either way."""
        if packing.resolve_dense(self.config.packing, self.alphabet):
            return packing.pack_text(s, self.alphabet,
                                     extra=2 * self.config.w_max + 8)
        return self._pad(s)

    # ---- worker units ------------------------------------------------------

    def process_group(self, s_padded, group, capacity: int,
                      pstats: PrepareStats | None = None,
                      group_index: int | None = None) -> list[SubTree]:
        """SubTreePrepare + slicing for ONE virtual tree (serial reference)."""
        state = subtree_prepare(s_padded, group, capacity,
                                self.config.elastic_config(), pstats,
                                group_index=group_index)
        return self._slice_subtrees(state, group)

    def process_groups(self, s_padded, groups, capacity: int,
                       pstats: PrepareStats | None = None) -> list[list[SubTree]]:
        """SubTreePrepare + slicing for MANY virtual trees through the
        shared batched (G, F) engine — one elastic loop for the whole set.
        Returns one ``list[SubTree]`` per input group."""
        states = subtree_prepare_batch(s_padded, groups, capacity,
                                       self.config.elastic_config(), pstats,
                                       sort_fuse=self.config.sort_fuse,
                                       compact=self.config.compaction)
        host = _HostState(states)
        return [self._slice_subtrees(host.group(g_i), g)
                for g_i, g in enumerate(groups)]

    @staticmethod
    def _slice_subtrees(state, group) -> list[SubTree]:
        ell = np.asarray(state.L)
        b_off = np.asarray(state.b_off)
        b_c1 = np.asarray(state.b_c1)
        b_c2 = np.asarray(state.b_c2)
        out = []
        for (off, f), p in zip(segments_of(group), group.prefixes):
            seg_b = b_off[off : off + f].copy()
            seg_b[0] = 0
            out.append(SubTree(
                prefix=p.symbols,
                ell=ell[off : off + f].copy(),
                b_off=seg_b,
                b_c1=b_c1[off : off + f].copy(),
                b_c2=b_c2[off : off + f].copy(),
            ))
        return out

    # ---- full builds -------------------------------------------------------

    def build(self, s: np.ndarray, report: BuildReport | None = None) -> SuffixTreeIndex:
        report = report if report is not None else BuildReport(VerticalStats(), PrepareStats())
        with obs.tracer().span("build/total", n=len(s),
                               engine=self.config.construction):
            if self.config.construction == "batched":
                return self._build_batched(s, report)
            return self._build_serial(s, report)

    def _build_serial(self, s: np.ndarray, report: BuildReport) -> SuffixTreeIndex:
        cfg = self.config
        groups = self.partition(s, report)
        capacity = self._capacity(groups)
        s_padded = self._device_text(s)

        t0 = time.perf_counter()
        subtrees: dict[tuple, SubTree] = {}
        for g_i, g in enumerate(groups):
            for st in self.process_group(s_padded, g, capacity, report.prepare,
                                         group_index=g_i):
                subtrees[st.prefix] = st
        report.t_prepare = time.perf_counter() - t0

        t0 = time.perf_counter()
        if cfg.build_impl != "none":
            builder = _BUILDERS[cfg.build_impl]
            n_total = len(s)
            for st in subtrees.values():
                st.nodes = builder(st.ell.astype(np.int32), st.b_off.astype(np.int32), n_total)
        report.t_build = time.perf_counter() - t0

        return SuffixTreeIndex(s=np.asarray(s), alphabet=self.alphabet, subtrees=subtrees)

    def _prepare_batched(self, s: np.ndarray, report: BuildReport):
        """partition → padded (G, F) batched prepare, timing into ``report``.

        Returns (groups, states, s_padded); states is None when the string
        produced no groups (cannot happen for a non-empty terminated
        string).  ``s_padded`` is the device text the prepare ran on, so
        downstream stages (the word-key node build) reuse it instead of
        re-packing.
        """
        groups = self.partition(s, report)
        if not groups:
            return groups, None, None
        capacity = self._capacity(groups)
        with obs.tracer().span("build/device_text", n=len(s)):
            s_padded = self._device_text(s)
        t0 = time.perf_counter()
        states = subtree_prepare_batch(s_padded, groups, capacity,
                                       self.config.elastic_config(),
                                       report.prepare,
                                       sort_fuse=self.config.sort_fuse,
                                       compact=self.config.compaction)
        report.t_prepare = time.perf_counter() - t0
        return groups, states, s_padded

    def _build_batched(self, s: np.ndarray, report: BuildReport) -> SuffixTreeIndex:
        cfg = self.config
        groups, states, s_padded = self._prepare_batched(s, report)
        subtrees: dict[tuple, SubTree] = {}
        if states is not None:
            t0 = time.perf_counter()
            host = _HostState(states)
            for g_i, g in enumerate(groups):
                for st in self._slice_subtrees(host.group(g_i), g):
                    subtrees[st.prefix] = st
            report.t_prepare += time.perf_counter() - t0

            t0 = time.perf_counter()
            if cfg.build_impl != "none":
                with obs.tracer().span("build/nodes",
                                       subtrees=len(subtrees),
                                       node_lcp=cfg.node_lcp):
                    self._attach_nodes_batched(states, groups, subtrees,
                                               len(s), s_text=s_padded)
            report.t_build = time.perf_counter() - t0

        return SuffixTreeIndex(s=np.asarray(s), alphabet=self.alphabet, subtrees=subtrees)

    def _attach_nodes_batched(self, states, groups, subtrees, n_total: int,
                              s_text=None) -> None:
        """All sub-trees' node sets via size-bucketed vmapped builds.

        Per-prefix (ell, b_off) segments are gathered on device into padded
        rows (depth-0 padding — see repro.core.build) and built with the
        vmapped parallel Cartesian-tree builder.  Rows are grouped into
        pad-width buckets whose COUNT is auto-tuned from the freq
        histogram (:func:`repro.core.build.bucket_pad_widths`: uniform
        mixes collapse to one bucket, heavy-tailed mixes split until
        another vmapped dispatch stops paying) instead of padding every
        row to the global max freq — on skewed prefix mixes the narrow
        buckets hold most rows at a fraction of the padded work, with
        bit-identical node sets per row either way.

        With ``EraConfig.node_lcp="words"`` (and a device text) the
        divergence rows come from the word-compare LCP on the text
        (:func:`repro.core.build.boff_rows_from_text`) instead of the
        stored ``b_off`` — bit-identical node sets, no dependence on the
        construction state's B entries.
        """
        use_words = self.config.node_lcp == "words" and s_text is not None
        entries = _sorted_segments(groups)
        f_cap = states.L.shape[1]
        flat_L = states.L.reshape(-1)
        flat_b = states.b_off.reshape(-1)
        fill_hist = obs.metrics().histogram(
            "build_bucket_fill_ratio",
            buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0),
            help="real cells / padded cells per node-build bucket "
                 "(low = the pow2 padding is wasting vmapped work)")
        for f_pad, rows in build_mod.bucket_pad_widths(
                [e[3] for e in entries]):
            fill = 0.0
            if obs.metrics_enabled() or obs.trace_enabled():
                real_cells = sum(entries[e_i][3] for e_i in rows)
                fill = real_cells / (len(rows) * f_pad)
                fill_hist.observe(fill)
            with obs.tracer().span("build/node_bucket", f_pad=f_pad,
                                   rows=len(rows), fill=round(fill, 4)):
                idx = np.zeros((len(rows), f_pad), np.int64)
                mask = np.zeros((len(rows), f_pad), bool)
                for r, e_i in enumerate(rows):
                    freq = entries[e_i][3]
                    idx[r, :freq] = _entry_flat_idx(entries[e_i], f_cap)
                    mask[r, :freq] = True
                idx = jnp.asarray(idx, jnp.int32)
                mask = jnp.asarray(mask)
                ell_rows = jnp.where(mask, jnp.take(flat_L, idx), n_total)
                if use_words:
                    boff_rows = build_mod.boff_rows_from_text(
                        s_text, ell_rows, n_total)
                else:
                    boff_rows = jnp.where(mask, jnp.take(flat_b, idx), 0)
                nodes = build_mod.build_parallel_batch(ell_rows, boff_rows,
                                                       n_total)
                parent = np.asarray(nodes.parent)
                depth = np.asarray(nodes.depth)
                witness = np.asarray(nodes.witness)
                for r, e_i in enumerate(rows):
                    prefix, _, _, freq = entries[e_i]
                    subtrees[prefix].nodes = build_mod.unpad_nodes_row(
                        parent[r], depth[r], witness[r], freq)

    def build_device(self, s: np.ndarray, report: BuildReport | None = None,
                     **device_kwargs):
        """String → :class:`repro.core.query.DeviceIndex` (the flattened
        batched query engine).

        With the batched engine the leaf arrays go straight from the
        (G, F) prepare state into suffix-array order with one device
        gather — no per-prefix numpy ``SubTree`` dict, no node build.  The
        serial engine builds the full index first and flattens it.
        ``device_kwargs``: ``route_cap``, ``max_pattern_len``, ``packing``
        (defaults to this indexer's ``EraConfig.packing``, so a dense
        build serves from the dense string).
        """
        report = report if report is not None else BuildReport(VerticalStats(), PrepareStats())
        device_kwargs.setdefault("packing", self.config.packing)
        if self.config.construction != "batched":
            return self.build(s, report).to_device(**device_kwargs)

        from repro.core.query import DeviceIndex  # local: avoid import cycle

        with obs.tracer().span("build/total", n=len(s), engine="batched"):
            groups, states, _ = self._prepare_batched(s, report)
            if states is None:
                raise ValueError("cannot flatten an empty index")
            with obs.tracer().span("build/flatten", groups=len(groups)):
                prefixes, freqs, ell = _flatten_state(groups, states)
                return DeviceIndex.from_prepare(
                    alphabet=self.alphabet,
                    s=np.asarray(s),
                    prefixes=prefixes,
                    freqs=freqs,
                    ell=ell,
                    **device_kwargs,
                )

    def build_stream(self, s: np.ndarray, report: BuildReport | None = None,
                     *, device_budget: int | None = None,
                     overlap: bool = True,
                     stream_report: StreamReport | None = None,
                     **device_kwargs):
        """String → :class:`repro.core.query.DeviceIndex` through the
        out-of-core streaming pipeline.

        Vertical-partition groups are sliced into chunks whose
        double-buffered (G_chunk, F) state fits ``device_budget`` bytes
        (:func:`repro.core.iomodel.plan_stream`), and the host→device
        copy of chunk k+1 overlaps the elastic-range loop of chunk k
        (:func:`repro.core.prepare.subtree_prepare_stream`).  The result
        is bit-identical to :meth:`build_device` — range choice never
        changes results — while peak device state is ~``2/n_chunks`` of
        the one-shot build's.  Returns ``(index, stream_report)``.
        """
        from repro.core.query import DeviceIndex  # local: avoid import cycle

        report = report if report is not None else BuildReport(
            VerticalStats(), PrepareStats())
        device_kwargs.setdefault("packing", self.config.packing)
        groups = self.partition(s, report)
        if not groups:
            raise ValueError("cannot flatten an empty index")
        capacity = self._capacity(groups)
        s_padded = self._device_text(s)
        t0 = time.perf_counter()
        states, srep = subtree_prepare_stream(
            s_padded, groups, capacity, self.config.elastic_config(),
            device_budget=device_budget, overlap=overlap,
            stats=report.prepare, report=stream_report,
            sort_fuse=self.config.sort_fuse,
            compact=self.config.compaction)
        report.t_prepare = time.perf_counter() - t0
        prefixes, freqs, ell = _flatten_state(groups, states)
        dev = DeviceIndex.from_prepare(
            alphabet=self.alphabet, s=np.asarray(s), prefixes=prefixes,
            freqs=freqs, ell=ell, **device_kwargs)
        return dev, srep

    # ---- incremental append ------------------------------------------------

    def _incremental_partition(self, s_new: np.ndarray, old_prefixes,
                               old_freqs, old_offs, old_ell,
                               n_old_real: int):
        """Derive ``s_new``'s vertical-partition prefix table from the OLD
        flat tables by rescanning only the *dirty window tail*.

        A window position's owning prefix depends on at most
        ``max_prefix_len`` symbols, so only positions in
        ``[n_old_real - max_prefix_len + 1, n_new_real]`` — the windows
        that used to read the old terminal plus every appended position —
        can change ownership or create occurrences.  Each dirty position
        walks the old prefix trie under S_new: landing on a member prefix
        bumps its count; falling off the trie (a branch that had zero
        occurrences before) creates a new survivor, exactly the node the
        full scan would keep.  Old occurrence lists come for free from the
        flat index: a sub-tree's ``ell`` segment IS its position set.

        A member (or fresh branch) whose updated count overflows ``f_max``
        splits locally: its merged position list is refined into children
        by gathering the next symbol — the same fixed point as the full
        scan's refinement phase, reached without touching clean positions.
        Returns ``(table, dirty_flags)`` — aligned lists of
        :class:`SubTreePrefix` and whether each sub-tree's leaf SET
        changed — or ``(None, None)`` in the one delta the local view
        cannot decide: an old EXPANDED node whose subtree count drops back
        to ``f_max`` or below, which the full scan would re-merge into a
        single sub-tree (shrinking appends don't exist, so this needs the
        terminal-tail occupancy to collapse — rare).  Frequencies are
        exact, so the fallback triggers iff the full scan would produce a
        different prefix set.
        """
        from repro.core.vertical import SubTreePrefix

        base = self.alphabet.base
        terminal = base - 1
        f_max = self.config.f_max
        n_new_real = len(s_new) - 1
        old_syms = [tuple(int(c) for c in p) for p in old_prefixes]
        max_plen = max(len(p) for p in old_syms)
        dirty_lo = max(0, n_old_real - max_plen + 1)

        members = set(old_syms)
        interior: set[tuple] = set()
        for p in old_syms:
            for t in range(1, len(p)):
                interior.add(p[:t])

        pad = np.full(max_plen + 2, terminal, np.uint8)
        sp = np.concatenate([np.asarray(s_new, np.uint8), pad])
        owned: dict[tuple, list[int]] = {}
        new_members: set[tuple] = set()
        for b in range(dirty_lo, n_new_real + 1):
            p: tuple = ()
            for t in range(max_plen + 1):
                p = p + (int(sp[b + t]),)
                if p in members or p in new_members:
                    owned.setdefault(p, []).append(b)
                    break
                if p in interior:
                    continue
                # first node off the old trie: the zero-frequency branch
                # the full scan would now keep as a fresh survivor
                new_members.add(p)
                owned.setdefault(p, []).append(b)
                break
            else:  # deeper than every old prefix: structure changed
                return None, None

        s_arr = np.asarray(s_new, np.uint8)

        def _next_sym(pos: np.ndarray, t: int) -> np.ndarray:
            """Symbol t past each position, terminal beyond the end (the
            window-code padding rule of :func:`vertical_partition`)."""
            idx = pos + t
            sym = np.full(pos.size, terminal, np.int64)
            inside = idx < s_arr.size
            sym[inside] = s_arr[idx[inside]]
            return sym

        table: list[SubTreePrefix] = []
        dirty_flags: list[bool] = []
        interior_freq: dict[tuple, int] = {}
        pending: list[tuple[tuple, np.ndarray]] = []  # overflows to split

        def _account(p: tuple, freq: int) -> None:
            for t in range(1, len(p)):
                q = p[:t]
                interior_freq[q] = interior_freq.get(q, 0) + freq

        for p, f, o in zip(old_syms, old_freqs, old_offs):
            seg = old_ell[int(o):int(o) + int(f)]
            lost = int((seg >= dirty_lo).sum())
            gained = owned.get(p, ())
            freq = int(f) - lost + len(gained)
            _account(p, freq)
            if freq == 0:
                continue                   # every occurrence moved away
            if lost or gained:
                keep = seg[seg < dirty_lo].astype(np.int64)
                pos = np.sort(np.concatenate(
                    [keep, np.asarray(gained, np.int64)]))
                if freq > f_max:
                    pending.append((p, pos))
                    continue
                table.append(SubTreePrefix(symbols=p, freq=freq,
                                           positions=pos))
                dirty_flags.append(True)
            else:
                table.append(SubTreePrefix(symbols=p, freq=freq,
                                           positions=seg.astype(np.int64)))
                dirty_flags.append(False)
        for p in sorted(new_members):
            pos = np.asarray(owned[p], np.int64)
            _account(p, int(pos.size))
            if pos.size > f_max:
                pending.append((p, pos))
                continue
            table.append(SubTreePrefix(symbols=p, freq=int(pos.size),
                                       positions=pos))
            dirty_flags.append(True)
        # every node the old scan expanded must still overflow, else the
        # full scan would KEEP it instead of its children
        if any(f <= f_max for f in interior_freq.values()):
            return None, None
        # local refinement of overflowing sub-trees (vertical phase 2 on
        # the merged position lists; masks keep positions ascending)
        while pending:
            p, pos = pending.pop()
            if pos.size == 0:
                continue
            if pos.size <= f_max:
                table.append(SubTreePrefix(symbols=p, freq=int(pos.size),
                                           positions=pos))
                dirty_flags.append(True)
                continue
            nxt = _next_sym(pos, len(p))
            for c in range(base):
                child = pos[nxt == c]
                if child.size:
                    pending.append((p + (c,), child))
        return table, dirty_flags

    def _append_merge(self, s_new: np.ndarray, old_prefixes, old_freqs,
                      old_offs, old_ell, count_fn, max_plen: int,
                      arep: AppendReport):
        """The shared append engine: rebuild only affected sub-trees of
        ``s_new``, reuse every other leaf segment of the old flat layout.

        A sub-tree of the NEW partition is *affected* (must be rebuilt on
        S_new) iff any of:

        * its prefix is new or its occurrence count changed (windows
          overlapping the appended region create occurrences the old
          index never saw);
        * its prefix contains the terminal symbol (the terminal moved);
        * it owns a suffix position in the terminal-affected tail
          ``[b*, n_old_real)`` (:func:`_terminal_affected_start`): those
          suffixes used to diverge at the old terminal, so their order
          within the sub-tree may change even though the leaf SET didn't.

        Every other sub-tree has the same leaf set AND the same sorted
        order as before (suffix pairs sharing its prefix diverge at real
        symbols in the common region), so its old ``ell`` segment is
        reused verbatim — which is what makes the merged index
        bit-identical to a full rebuild.
        """
        terminal = self.alphabet.base - 1
        n_old_real = int(np.asarray(old_freqs, np.int64).sum()) - 1
        n_new_real = len(s_new) - 1
        if int(s_new[-1]) != terminal:
            raise ValueError("appended string must end with the terminal")
        if n_new_real <= n_old_real:
            raise ValueError(
                f"append needs new symbols: |S_new|={n_new_real} real "
                f"symbols vs |S_old|={n_old_real}")
        arep.n_old = n_old_real
        arep.n_new = n_new_real

        t0 = time.perf_counter()
        b_star = _terminal_affected_start(count_fn, s_new, n_old_real,
                                          max_plen)
        arep.b_star = b_star
        arep.t_scan = time.perf_counter() - t0

        t0 = time.perf_counter()
        table, dirty_flags = self._incremental_partition(
            s_new, old_prefixes, old_freqs, old_offs, old_ell, n_old_real)
        if table is None:  # split structure changed: full scan (rare)
            arep.partition_fallback = True
            breport = BuildReport(VerticalStats(), PrepareStats())
            groups_new = self.partition(s_new, breport)
            table = [p for g in groups_new for p in g.prefixes]
            dirty_flags = None
        arep.t_partition = time.perf_counter() - t0

        old_map = {p: (int(f), int(o))
                   for p, f, o in zip(old_prefixes, old_freqs, old_offs)}
        all_prefixes = table
        affected = []
        with obs.tracer().span("append/classify", prefixes=len(table),
                               fallback=int(dirty_flags is None)) as sp:
            for i, p in enumerate(all_prefixes):
                old = old_map.get(p.symbols)
                if dirty_flags is not None:
                    # incremental table: leaf-set changes are already
                    # flagged; an unchanged set still rebuilds when any
                    # suffix lies in the terminal-comparison tail
                    changed = dirty_flags[i]
                    if not changed and bool(
                            ((p.positions >= b_star)
                             & (p.positions < n_old_real)).any()):
                        p.positions = np.sort(p.positions)
                        changed = True
                elif (old is None or old[0] != p.freq
                        or terminal in p.symbols
                        or bool(((p.positions >= b_star)
                                 & (p.positions < n_old_real)).any())):
                    changed = True
                else:
                    changed = False
                if changed:
                    affected.append(p)
            sp.set(affected=len(affected), b_star=b_star)
        arep.n_prefixes = len(all_prefixes)
        arep.n_affected = len(affected)

        rebuilt: dict[tuple, np.ndarray] = {}
        if affected:
            from repro.core.vertical import group_prefixes
            t0 = time.perf_counter()
            re_groups = group_prefixes(affected, self.config.f_max)
            capacity = min(self.config.f_max,
                           max(g.total_freq for g in re_groups))
            s_padded = self._device_text(s_new)
            with obs.tracer().span("append/prepare",
                                   groups=len(re_groups),
                                   subtrees=len(affected)):
                states = subtree_prepare_batch(
                    s_padded, re_groups, capacity,
                    self.config.elastic_config(),
                    sort_fuse=self.config.sort_fuse,
                    compact=self.config.compaction)
            L_host = np.asarray(states.L)
            for g_i, g in enumerate(re_groups):
                for (off, freq), p in zip(segments_of(g), g.prefixes):
                    rebuilt[p.symbols] = L_host[g_i, off:off + freq]
            arep.t_prepare = time.perf_counter() - t0

        t0 = time.perf_counter()
        order = sorted(range(len(all_prefixes)),
                       key=lambda i: all_prefixes[i].symbols)
        segs, pref_out, freq_out = [], [], []
        reused = 0
        for i in order:
            p = all_prefixes[i]
            seg = rebuilt.get(p.symbols)
            if seg is None:
                f, o = old_map[p.symbols]
                seg = old_ell[o:o + f]
                reused += f
            segs.append(np.asarray(seg, np.int32))
            pref_out.append(p.symbols)
            freq_out.append(p.freq)
        ell = np.concatenate(segs).astype(np.int32)
        arep.leaves_reused = reused
        arep.leaves_rebuilt = int(ell.size) - reused
        arep.t_merge = time.perf_counter() - t0
        return pref_out, np.asarray(freq_out, np.int32), ell

    @staticmethod
    def _check_append_prefix(old_codes: np.ndarray, s_new: np.ndarray,
                             n_old_real: int) -> None:
        if not np.array_equal(np.asarray(s_new[:n_old_real], np.uint8),
                              np.asarray(old_codes[:n_old_real], np.uint8)):
            raise ValueError(
                "append requires S_new to extend the indexed string: the "
                f"first {n_old_real} symbols differ")

    def append_device(self, dev, s_new: np.ndarray,
                      report: AppendReport | None = None, **device_kwargs):
        """Incrementally extend a :class:`DeviceIndex` over ``S_old`` to
        index ``s_new`` (= S_old's real symbols + appended symbols +
        terminal) WITHOUT a full rebuild.

        Only the affected sub-trees run the elastic-range loop (see
        :meth:`_append_merge`); unaffected leaf segments are copied from
        the old index.  The result is bit-identical to
        ``build_device(s_new)`` with the same flatten kwargs, carries
        ``epoch = dev.epoch + 1`` so serving caches invalidate, and
        returns ``(index, append_report)``.
        """
        from repro.core.query import DeviceIndex  # local: avoid import cycle

        s_new = np.asarray(s_new)
        arep = report if report is not None else AppendReport()
        plen = np.asarray(dev.sub_plen)
        pref = np.asarray(dev.sub_prefix)
        old_prefixes = [tuple(int(c) for c in pref[t, :plen[t]])
                        for t in range(len(plen))]
        old_freqs = np.asarray(dev.sub_freq)
        old_offs = np.asarray(dev.sub_off)
        self._check_append_prefix(dev.string_codes(), s_new,
                                  int(old_freqs.sum()) - 1)

        def count_fn(pats):
            padded, lengths, route = dev.pad_batch(pats)
            _, cnt = dev.find_batch_ranges(padded, lengths, route)
            return np.asarray(cnt)

        with obs.tracer().span("append/total", n_old=dev.n_leaves - 1,
                               n_new=len(s_new) - 1):
            prefixes, freqs, ell = self._append_merge(
                s_new, old_prefixes, old_freqs, old_offs, dev.ell_host,
                count_fn, dev.max_pattern_len, arep)
            device_kwargs.setdefault("packing", self.config.packing)
            device_kwargs.setdefault("max_pattern_len", dev.max_pattern_len)
            device_kwargs.setdefault("epoch", dev.epoch + 1)
            new_dev = DeviceIndex.from_prepare(
                alphabet=self.alphabet, s=s_new, prefixes=prefixes,
                freqs=freqs, ell=ell, **device_kwargs)
        return new_dev, arep

    def append_sharded(self, sharded, s_new: np.ndarray,
                       report: AppendReport | None = None, *,
                       n_shards: int | None = None, **device_kwargs):
        """Incremental append for a :class:`repro.core.fabric.ShardedIndex`.

        The route-ordered per-shard tables concatenate into exactly the
        single-device flat layout (``ShardedIndex.flat_table``), the same
        merge runs there, and the merged layout re-shards through the
        route-interval planner (``ShardedIndex.from_flat`` /
        ``plan_shards``) — so per-shard ``…_shard{k}.npz`` archives
        refresh without any shard ever rebuilding its unaffected
        segments.  Returns ``(sharded_index, append_report)``.
        """
        from repro.core import fabric  # local: avoid import cycle

        s_new = np.asarray(s_new)
        arep = report if report is not None else AppendReport()
        old_prefixes, old_freqs, old_ell = sharded.flat_table()
        old_offs = np.concatenate(
            [[0], np.cumsum(old_freqs)[:-1]]).astype(np.int64)
        self._check_append_prefix(sharded.string_codes(), s_new,
                                  int(old_freqs.sum()) - 1)

        def count_fn(pats):
            return np.asarray([len(h) for h in sharded.find_batch(pats)],
                              np.int64)

        with obs.tracer().span("append/total", n_old=sharded.n_leaves - 1,
                               n_new=len(s_new) - 1, shards=sharded.n_shards):
            prefixes, freqs, ell = self._append_merge(
                s_new, old_prefixes, old_freqs, old_offs, old_ell,
                count_fn, sharded.max_pattern_len, arep)
            device_kwargs.setdefault("packing", self.config.packing)
            device_kwargs.setdefault("max_pattern_len",
                                     sharded.max_pattern_len)
            device_kwargs.setdefault("epoch", sharded.epoch + 1)
            new_idx = fabric.ShardedIndex.from_flat(
                alphabet=self.alphabet, s=s_new, prefixes=prefixes,
                freqs=freqs, ell=ell,
                n_shards=n_shards or sharded.n_shards, **device_kwargs)
        return new_idx, arep

    def build_sharded(self, s: np.ndarray, n_shards: int | None = None,
                      report: BuildReport | None = None, *,
                      mesh=None, sort_fuse: bool | None = None,
                      **device_kwargs):
        """String → :class:`repro.core.fabric.ShardedIndex`: SPMD
        construction over the device mesh, then the flattened leaf
        arrays sharded by top-trie route key.

        ``n_shards`` defaults to the mesh size (all local devices); the
        construction mesh and the index shard count are independent —
        group blocks parallelize the elastic loop, route-key shards
        partition the query fabric.  Results are bit-identical to
        :meth:`build_device` (same flatten, same probe) — see
        tests/test_fabric.py.
        """
        from repro.core import fabric  # local: avoid import cycle

        report = report if report is not None else BuildReport(
            VerticalStats(), PrepareStats())
        device_kwargs.setdefault("packing", self.config.packing)
        mesh = mesh or fabric.fabric_mesh()
        if n_shards is None:
            n_shards = mesh.devices.size
        groups = self.partition(s, report)
        if not groups:
            raise ValueError("cannot shard an empty index")
        capacity = self._capacity(groups)
        s_padded = self._device_text(s)
        t0 = time.perf_counter()
        states = fabric.sharded_prepare(
            s_padded, groups, capacity, self.config.elastic_config(),
            mesh=mesh, stats=report.prepare,
            sort_fuse=(sort_fuse if sort_fuse is not None
                       else self.config.sort_fuse),
            compact=self.config.compaction)
        report.t_prepare = time.perf_counter() - t0
        prefixes, freqs, ell = _flatten_state(groups, states)
        return fabric.ShardedIndex.from_flat(
            alphabet=self.alphabet, s=np.asarray(s),
            prefixes=prefixes, freqs=freqs,
            ell=ell, n_shards=n_shards, **device_kwargs)

    def build_analytics(self, s: np.ndarray, report: BuildReport | None = None,
                        **device_kwargs):
        """Build + flatten + LCP in one step: returns ``(index, engine)``
        where the second element is the device-resident analytics engine
        (:class:`repro.core.analytics.AnalyticsEngine`).  Flattening
        kwargs default ``packing`` to this indexer's config."""
        index = self.build(s, report)
        if device_kwargs or self.config.packing != "auto":
            # honor a non-default packing even on the no-kwargs path (the
            # engine is then built uncached; "auto" keeps the shared cache,
            # whose default is the same "auto")
            device_kwargs.setdefault("packing", self.config.packing)
        return index, index.analytics(**device_kwargs)


class _HostState:
    """One bulk device→host transfer of a (G, F) state, sliceable per group."""

    def __init__(self, states):
        self.L = np.asarray(states.L)
        self.b_off = np.asarray(states.b_off)
        self.b_c1 = np.asarray(states.b_c1)
        self.b_c2 = np.asarray(states.b_c2)

    def group(self, g_i: int) -> "_HostState":
        view = object.__new__(_HostState)
        view.L = self.L[g_i]
        view.b_off = self.b_off[g_i]
        view.b_c1 = self.b_c1[g_i]
        view.b_c2 = self.b_c2[g_i]
        return view

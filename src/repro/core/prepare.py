"""SubTreePrepare (paper §4.2.2) — elastic-range batched construction in JAX.

The paper's algorithm maintains, for one virtual tree, arrays ``L`` (leaf
positions, progressively reordered into lexicographic suffix order), ``A``
(active areas), ``B`` (branching triplets) and a read buffer ``R``.  Each
iteration reads ``range`` symbols after every *active* leaf, sorts active
areas lexicographically, and emits ``B[i] = (c1, c2, offset)`` where two
adjacent branches diverge.  ``range = |R| / |active|`` grows as leaves
resolve — the *elastic range*.

TPU-native formulation implemented here:

* the per-leaf read becomes a batched gather (``range_gather_pack``): ``w``
  symbols per active leaf, packed big-endian 4-symbols/int32 so that integer
  order == lexicographic order (terminal ``$`` = largest code, matching the
  paper's traces; S is terminal-padded so overruns are safe — two distinct
  suffixes always diverge at or before the earlier ``$``);
* the per-area reorder becomes ONE stable ``jnp.lexsort`` over the whole
  state with the area id as the major key.  Done elements get a unique
  singleton major key (their own index) so they never move — this preserves
  the paper's invariant that resolved positions are frozen;
* divergence detection becomes a vectorized adjacent-row LCP on the packed
  words (``lcp_adjacent``);
* areas / done flags are recomputed with a cumulative-max segment sweep.

``B`` entries are attached to *positions* (boundaries), which is sound
because areas only ever split in place: once positions ``i-1 | i`` are
separated, the boundary index never moves again.

Shapes are static per jitted step; the elastic range ``w`` is bucketed to
powers of two so at most ``log2(w_max/w_min)`` distinct compilations occur.
The host loop drives steps until every area is resolved.

Two drivers share the step: :func:`subtree_prepare` runs one virtual tree
(the reference / worked-example path) and :func:`subtree_prepare_batch`
stacks every group into one padded (G, F) state and drives a single
vmapped, buffer-donated loop — the default construction engine (paper §5:
virtual trees are independent, so the batch axis is free parallelism and
``shard_map`` over G distributes it across devices).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import packing
from repro.core.packing import (  # noqa: F401  (re-exported; shared with build/query)
    PackedText,
    as_u32,
    clz32 as _clz32,
    gather_pack,
    pack_words,
)
from repro.core.vertical import VirtualTree
from repro.kernels import ops as kops

DONE = jnp.int32(-1)
UNDEF = jnp.int32(-1)


class PrepareState(NamedTuple):
    """Per-virtual-tree state; all arrays have static length F (padded)."""

    L: jax.Array       # int32[F]  leaf positions (suffix offsets), -1 pad
    start: jax.Array   # int32[F]  symbols consumed so far per element
    area: jax.Array    # int32[F]  active-area id (= index of first element), -1 done
    b_off: jax.Array   # int32[F]  B offset, -1 undefined (b_*[0] unused)
    b_c1: jax.Array    # int32[F]  first divergent symbol of left branch
    b_c2: jax.Array    # int32[F]  first divergent symbol of right branch


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """Memory-budget knobs (paper §4.4)."""

    r_budget_symbols: int = 1 << 20  # |R|: total symbols fetched per scan
    w_min: int = 4
    w_max: int = 256
    elastic: bool = True  # False = static range (paper Fig. 9b ablation)
    static_w: int = 16


def _init_arrays(group: VirtualTree, capacity: int):
    """Host-side (L, start, area) arrays for one group (padded to capacity)."""
    total = sum(p.freq for p in group.prefixes)
    if total > capacity:
        raise ValueError(f"group frequency {total} exceeds capacity {capacity}")
    L = np.full(capacity, -1, dtype=np.int32)
    start = np.zeros(capacity, dtype=np.int32)
    area = np.full(capacity, -1, dtype=np.int32)
    off = 0
    for p in group.prefixes:
        f = p.freq
        L[off : off + f] = p.positions
        start[off : off + f] = p.length
        if f > 1:
            area[off : off + f] = off
        off += f
    return L, start, area


def init_state(group: VirtualTree, capacity: int) -> PrepareState:
    """Concatenate the group's occurrence lists into padded state arrays.

    Each prefix's segment gets its own initial area (id = segment start);
    frequency-1 prefixes are born resolved (a single leaf is a complete
    sub-tree).
    """
    L, start, area = _init_arrays(group, capacity)
    return PrepareState(
        L=jnp.asarray(L),
        start=jnp.asarray(start),
        area=jnp.asarray(area),
        b_off=jnp.full(capacity, -1, jnp.int32),
        b_c1=jnp.zeros(capacity, jnp.int32),
        b_c2=jnp.zeros(capacity, jnp.int32),
    )


def _host_init_batch(groups: list[VirtualTree], capacity: int) -> PrepareState:
    """Host-side (numpy) stacked (G, F) state — the unit the streaming
    pipeline stages through pinned buffers before ``jax.device_put``."""
    if not groups:
        raise ValueError("init_batch needs at least one group")
    cols = [_init_arrays(g, capacity) for g in groups]
    g = len(groups)
    return PrepareState(
        L=np.stack([c[0] for c in cols]),
        start=np.stack([c[1] for c in cols]),
        area=np.stack([c[2] for c in cols]),
        b_off=np.full((g, capacity), -1, np.int32),
        b_c1=np.zeros((g, capacity), np.int32),
        b_c2=np.zeros((g, capacity), np.int32),
    )


def init_batch(groups: list[VirtualTree], capacity: int) -> PrepareState:
    """Stack ALL groups into one padded (G, F) state for the batched engine."""
    host = _host_init_batch(groups, capacity)
    return PrepareState(*(jnp.asarray(a) for a in host))


# ---------------------------------------------------------------------------
# Packed-key helpers — one shared implementation in core.packing, re-exported
# here (``pack_words`` / ``gather_pack``) for existing importers.
# ---------------------------------------------------------------------------


def lcp_adjacent(keys: jax.Array, w: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """LCP (in symbols) + first divergent symbols between adjacent rows.

    keys: (F, W) int32 packed words.  Returns (lcp, c1, c2) each (F,) where
    entry i compares rows i-1 and i (entry 0 is garbage, callers mask it).
    """
    a = jnp.concatenate([keys[:1], keys[:-1]], axis=0)  # row i-1
    b = keys
    neq = a != b
    any_neq = jnp.any(neq, axis=1)
    word = jnp.argmax(neq, axis=1).astype(jnp.int32)  # first differing word
    aw = jnp.take_along_axis(a, word[:, None], axis=1)[:, 0]
    bw = jnp.take_along_axis(b, word[:, None], axis=1)[:, 0]
    x = aw ^ bw
    byte = _clz32(x) // 8  # byte index from the top (0..3); x>0 when any_neq
    lcp = jnp.where(any_neq, word * 4 + byte, w).astype(jnp.int32)
    shift = (3 - byte) * 8
    c1 = (aw >> shift) & 0xFF
    c2 = (bw >> shift) & 0xFF
    return lcp, c1.astype(jnp.int32), c2.astype(jnp.int32)


# ---------------------------------------------------------------------------
# One elastic-range step (jitted per static w)
# ---------------------------------------------------------------------------

def _kernel_impls(use_pallas: bool):
    """Select kernel implementations; a STATIC jit arg so switching the
    REPRO_KERNELS env var between builds cannot hit a stale trace cache.

    The returned gather dispatches on the string representation: a dense
    :class:`repro.core.packing.PackedText` (paper §6.1 generalized —
    ``8/bits``x less gather traffic) or the terminal-padded byte array.
    Both emit identical byte-per-symbol sort keys, so the LCP stage is
    shared and construction output is representation-independent."""
    if use_pallas:
        from repro.kernels.lcp import lcp_pairs as lcp_k

        return kops.range_gather_impl(True), lcp_k
    from repro.kernels import ref as kref

    return kops.range_gather_impl(False), kref.lcp_pairs_ref


def _fused_sort_order(major, keys, tie, *, w: int, bits: int,
                      f: int) -> jax.Array | None:
    """Stable sort order on (major, window, tie) packed into the fewest
    uint32 lanes — the fabric engine's sort-key fusion.

    The lexsort path compares ``2 + n_words`` operands (tie + every dense
    word + the area major).  But the triple is just one big integer:
    ``major`` needs ceil(log2 F) bits, the window exactly ``w*bits``
    meaningful bits (top-aligned in the words), ``tie`` ceil(log2(w+1)).
    Bit-concatenating them yields ceil(total/32) lanes — ONE lane for the
    hot small-``w`` iterations of a 2-bit alphabet, and always at least
    one fewer comparator operand than lexsort.

    The fused key drops each word's bits BEYOND ``w`` symbols, which the
    lexsort path does feed to the comparator; by the step's documented
    invariant those extra bits only reorder rows INSIDE still-active
    equal-window blocks, which later iterations re-sort before anything
    observable is emitted — final construction arrays are bit-identical
    (pinned by tests/test_fabric.py).

    Returns None when the packing cannot beat lexsort (major + tie alone
    overflow one lane — F beyond ~2^26 with w = 64).
    """
    mb = max(1, int(np.ceil(np.log2(max(f, 2)))))
    tb = max(1, int(np.ceil(np.log2(w + 2))))
    if mb + tb > 32:
        return None
    kw = w * bits
    total = mb + kw + tb
    n_lanes = -(-total // 32)
    lanes = [jnp.zeros(major.shape, jnp.uint32) for _ in range(n_lanes)]

    def place(value, pos, width):
        # OR a right-aligned ``width``-bit field into the conceptual
        # bitstring at MSB-offset ``pos`` (lane bitrange [32j, 32j+32))
        end = pos + width
        lane0, lane1 = pos // 32, (end - 1) // 32
        if lane0 == lane1:
            lanes[lane0] = lanes[lane0] | (value << (32 * (lane0 + 1) - end))
        else:  # field straddles a lane boundary: split high/low
            lanes[lane0] = lanes[lane0] | (value >> (end - 32 * (lane0 + 1)))
            lanes[lane1] = lanes[lane1] | (value << (32 * (lane1 + 1) - end))

    place(major.astype(jnp.uint32), 0, mb)
    for j in range(keys.shape[1]):
        m_j = min(32, kw - 32 * j)  # meaningful top bits of word j
        place(keys[:, j] >> (32 - m_j), mb + 32 * j, m_j)
    place(tie.astype(jnp.uint32), mb + kw, tb)
    return jnp.lexsort(tuple(lanes[::-1]))


def prepare_step(s_padded, state: PrepareState, *, w: int,
                 use_pallas: bool = False,
                 word_keys: bool | None = None,
                 sort_fuse: bool = False,
                 gather_fn=None) -> tuple[PrepareState, jax.Array]:
    """One iteration of SubTreePrepare for static range ``w``.

    ``s_padded``: the terminal-padded byte string OR a dense
    :class:`repro.core.packing.PackedText` — results are bit-identical.
    For a PackedText the sort runs on the dense uint32 WORD keys by
    default (``word_keys``; env ``REPRO_WORD_COMPARE=byte`` or an
    explicit ``False`` pins the byte-key oracle): ``8/bits``x fewer sort
    key words plus one ``w - limit`` tiebreak lane, identical final
    construction arrays (intermediate orders may differ only INSIDE
    still-active equal-key blocks, which the segmented sort re-orders
    before anything observable is emitted).

    ``sort_fuse`` (the sharded fabric's default) packs the whole
    (major, window, tie) triple into the fewest uint32 sort lanes
    (:func:`_fused_sort_order`) — same final arrays, fewer comparator
    operands; it applies only on the word-key path and silently falls
    back to lexsort elsewhere.
    Returns (new_state, n_active).
    """
    f = state.L.shape[0]
    iota = jnp.arange(f, dtype=jnp.int32)
    active = state.area >= 0
    if word_keys is None:
        word_keys = kops._use_word_compare()
    word_keys = (word_keys and isinstance(s_padded, PackedText)
                 and gather_fn is None)

    offs = jnp.where(active, state.L + state.start, 0)
    major = jnp.where(active, state.area, iota)

    if word_keys:
        # 1w. read the dense word keys directly (no byte repack): the
        #     substituted words plus the w - limit tiebreak ARE the
        #     comparison currency (see core.packing's word-compare rules).
        keys, tie = packing.word_sort_keys(
            s_padded, offs, w,
            gather_words=kops.range_gather_words_impl(use_pallas))
        keys = jnp.where(active[:, None], keys, jnp.uint32(0))
        tie = jnp.where(active, tie, 0)

        # 2w. segmented stable sort on ``8/bits``x fewer minor words; the
        #     tiebreak lane is the LEAST significant key.
        order = None
        if sort_fuse:
            order = _fused_sort_order(major, keys, tie, w=w,
                                      bits=s_padded.bits, f=f)
        if order is None:
            n_words = keys.shape[1]
            minor_keys = (tie,) + tuple(keys[:, j]
                                        for j in range(n_words - 1, -1, -1))
            order = jnp.lexsort(minor_keys + (major,))
        L = state.L[order]
        start = state.start[order]
        keys = keys[order]

        # 3w. adjacent divergence: XOR + clz + terminal-limit rules give
        #     the same (lcp, c1, c2) the byte rows would.
        lim = packing.word_limit(s_padded.n_real, L + start, w)
        prev_rows = jnp.concatenate([keys[:1], keys[:-1]], axis=0)
        prev_lim = jnp.concatenate([lim[:1], lim[:-1]])
        lcp, c1, c2 = packing.lcp_adjacent_words(
            prev_rows, keys, prev_lim, lim, w, s_padded.bits,
            s_padded.terminal)
    else:
        # 1. read ``w`` symbols after every active leaf (paper lines 9-12);
        #    Pallas paged-gather on TPU, pure-jnp fallback elsewhere.
        default_gather, lcp_fn = _kernel_impls(use_pallas)
        gather_fn = gather_fn or default_gather
        keys = gather_fn(s_padded, offs, w)
        keys = jnp.where(active[:, None], keys, 0)

        # 2. segmented stable sort (paper lines 13-15): major key = area
        #    id; done elements get singleton majors (their index) so they
        #    stay put.  Minor keys compare as uint32: byte-alphabet codes
        #    >= 128 set the int32 sign bit of the top packed byte, so
        #    signed order would break.
        sort_keys = as_u32(keys) if keys.dtype == jnp.int32 else keys
        n_words = keys.shape[1]
        minor_keys = tuple(sort_keys[:, j] for j in range(n_words - 1, -1, -1))
        order = jnp.lexsort(minor_keys + (major,))
        L = state.L[order]
        start = state.start[order]
        keys = keys[order]
        # area / b_* are position-attached: within-area sorting leaves
        # them fixed.

        # 3. adjacent divergence → B entries (paper lines 16-23)
        prev_rows = jnp.concatenate([keys[:1], keys[:-1]], axis=0)
        lcp, c1, c2 = lcp_fn(prev_rows, keys, w)

    same_area = (state.area == jnp.roll(state.area, 1)) & active & (iota > 0)
    new_split = same_area & (lcp < w)
    b_off = jnp.where(new_split, start + lcp, state.b_off)
    b_c1 = jnp.where(new_split, c1, state.b_c1)
    b_c2 = jnp.where(new_split, c2, state.b_c2)

    # 4. recompute areas: a run starts where the old area changes or a new
    #    split landed; singleton runs are done (leaf found, Prop. 1 case 1).
    run_start = active & (
        (iota == 0)
        | (state.area != jnp.roll(state.area, 1))
        | ~jnp.roll(active, 1)
        | new_split
    )
    seg = jax.lax.cummax(jnp.where(run_start, iota, -1))
    nxt_start = jnp.concatenate([run_start[1:], jnp.array([True])])
    nxt_active = jnp.concatenate([active[1:], jnp.array([False])])
    right_bound = nxt_start | ~nxt_active
    singleton = run_start & right_bound
    area = jnp.where(active & ~singleton, seg, DONE)

    # 5. elastic advance for survivors
    start = jnp.where(area >= 0, start + w, start)

    new_state = PrepareState(L=L, start=start, area=area,
                             b_off=b_off, b_c1=b_c1, b_c2=b_c2)
    return new_state, jnp.sum(area >= 0)


@functools.partial(jax.jit, static_argnames=("w", "use_pallas", "word_keys"))
def _jit_step(s_padded, state, w, use_pallas=False, word_keys=None):
    return prepare_step(s_padded, state, w=w, use_pallas=use_pallas,
                        word_keys=word_keys)


def prepare_step_batch(s_padded, states: PrepareState, *, w: int,
                       use_pallas: bool = False,
                       word_keys: bool | None = None,
                       sort_fuse: bool = False):
    """One elastic-range iteration for a (G, F) batch of virtual trees.

    Groups are independent, so the step is a plain vmap over the leading
    axis; converged groups have no active areas, make zeroed gathers and
    are exact fixed points of the step.  Callers may shard_map G over the
    mesh — the only cross-device data is the replicated string read
    (byte array or dense PackedText; the latter replicates ``8/bits``x
    fewer bytes per device); :func:`repro.core.fabric.sharded_prepare`
    is that driver.

    Returns (new_states, n_active) with ``n_active`` int32[G].
    """
    step = lambda st: prepare_step(s_padded, st, w=w, use_pallas=use_pallas,
                                   word_keys=word_keys, sort_fuse=sort_fuse)
    return jax.vmap(step)(states)


@functools.partial(jax.jit,
                   static_argnames=("w", "use_pallas", "word_keys",
                                    "sort_fuse"),
                   donate_argnums=(1,))
def _jit_step_batch(s_padded, states, w, use_pallas=False, word_keys=None,
                    sort_fuse=False):
    # donated state buffers: the host loop re-binds the result, so the
    # whole elastic loop runs in-place on device.
    return prepare_step_batch(s_padded, states, w=w, use_pallas=use_pallas,
                              word_keys=word_keys, sort_fuse=sort_fuse)


def compact_step_batch(s_padded, states: PrepareState, *, f_prime: int,
                       w: int, use_pallas: bool, word_keys: bool,
                       sort_fuse: bool):
    """One elastic iteration on only the ACTIVE rows of each group.

    Tail iterations sort a (G, F) state in which most rows are long done;
    the sort is the whole step cost, so the engine gathers each group's
    active rows (ascending, so contiguous area blocks stay contiguous and
    in order) into a (G, f_prime) buffer, runs the UNMODIFIED
    :func:`prepare_step` there, and scatters the results back.  Exactness:
    the step's only position-dependent quantity is ``area`` (the run-start
    position), which translates through the gather index map both ways;
    ``b_off`` is a string offset, not a position; and every
    adjacency-based rule (``same_area``/``run_start``/``right_bound``)
    sees the same neighbor pairs because done rows only ever SEPARATE
    blocks, never join them.  ``f_prime`` must be >= every group's active
    count (:func:`compaction_width` buckets the global max to a power of
    two).  Proven in the sharded fabric (PR 8); now the shared batched
    step every driver — batched, streaming, append, fabric — compacts
    through.
    """
    f = states.area.shape[1]

    def one_group(st):
        active = st.area >= 0
        idx = jnp.nonzero(active, size=f_prime, fill_value=f)[0]
        valid = idx < f
        safe = jnp.minimum(idx, f - 1).astype(jnp.int32)
        take = lambda x, fill: jnp.where(valid, x[safe], fill)
        # run-start positions -> compacted positions (run starts are
        # themselves active rows, so searchsorted finds them exactly)
        carea = jnp.where(
            valid,
            jnp.searchsorted(idx, take(st.area, 0).clip(0)).astype(
                st.area.dtype),
            DONE)
        cst = PrepareState(L=take(st.L, -1), start=take(st.start, 0),
                           area=carea, b_off=take(st.b_off, -1),
                           b_c1=take(st.b_c1, 0), b_c2=take(st.b_c2, 0))
        new, _ = prepare_step(s_padded, cst, w=w, use_pallas=use_pallas,
                              word_keys=word_keys, sort_fuse=sort_fuse)
        # compacted run starts -> full-layout positions
        narea = jnp.where(
            new.area >= 0,
            idx[jnp.maximum(new.area, 0)].astype(new.area.dtype), DONE)
        scat = jnp.where(valid, idx, f)  # out-of-bounds pads drop
        put = lambda full, vals: full.at[scat].set(vals, mode="drop")
        return PrepareState(L=put(st.L, new.L),
                            start=put(st.start, new.start),
                            area=put(st.area, narea),
                            b_off=put(st.b_off, new.b_off),
                            b_c1=put(st.b_c1, new.b_c1),
                            b_c2=put(st.b_c2, new.b_c2))

    new_states = jax.vmap(one_group)(states)
    return new_states, jnp.sum(new_states.area >= 0, axis=1)


@functools.partial(jax.jit,
                   static_argnames=("w", "use_pallas", "word_keys",
                                    "sort_fuse", "f_prime"),
                   donate_argnums=(1,))
def _jit_compact_step_batch(s_padded, states, w, use_pallas=False,
                            word_keys=None, sort_fuse=False, f_prime=32):
    return compact_step_batch(s_padded, states, f_prime=f_prime, w=w,
                              use_pallas=use_pallas, word_keys=word_keys,
                              sort_fuse=sort_fuse)


def compaction_width(maxact: int, capacity: int) -> int | None:
    """The compacted row width for a global max active count — the pow2
    bucket keeps jit program variants to ~log2(F) per w — or None while
    compaction cannot beat the full-width step (active rows still fill
    more than half the state)."""
    f_prime = max(32, 1 << max(maxact - 1, 0).bit_length())
    return None if f_prime * 2 > capacity else f_prime


def elastic_range(cfg: ElasticConfig, n_active: int) -> int:
    """range = |R| / |L'| (paper §4.4), bucketed to a power of two."""
    if not cfg.elastic:
        return max(4, (cfg.static_w + 3) // 4 * 4)
    w = max(cfg.w_min, min(cfg.w_max, cfg.r_budget_symbols // max(1, n_active)))
    return 1 << int(np.floor(np.log2(w)))


@dataclasses.dataclass
class PrepareStats:
    iterations: int = 0
    ranges: list = dataclasses.field(default_factory=list)
    active_history: list = dataclasses.field(default_factory=list)
    symbols_fetched: int = 0
    record_offsets: bool = False  # keep per-iteration offsets for iomodel
    offsets_history: list = dataclasses.field(default_factory=list)


def _record_prepare_metrics(group_iters: list, wall_s: float,
                            cfg: ElasticConfig) -> None:
    """Registry rows for one completed prepare run: per-group elastic
    iteration counts (the paper's convergence constant, a histogram so
    skew is visible) plus total convergence wall time."""
    if not obs.metrics_enabled():
        return
    m = obs.metrics()
    h = m.histogram("prepare_group_iterations",
                    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
                    help="elastic-range iterations until each virtual "
                         "tree converged")
    for it in group_iters:
        h.observe(it)
    m.counter("prepare_convergence_seconds_total",
              "wall time spent in elastic-range loops").inc(wall_s)
    m.counter("prepare_runs_total",
              "completed SubTreePrepare loops").inc()
    m.gauge("prepare_r_budget_symbols",
            "|R| read-buffer budget of the last run").set(
        cfg.r_budget_symbols)


def subtree_prepare(
    s_padded,
    group: VirtualTree,
    capacity: int,
    cfg: ElasticConfig = ElasticConfig(),
    stats: PrepareStats | None = None,
    max_iters: int = 10_000,
    group_index: int | None = None,
) -> PrepareState:
    """Run SubTreePrepare to completion for one virtual tree."""
    state = init_state(group, capacity)
    use_pallas = kops._use_pallas()
    word_keys = kops._use_word_compare()
    n_active = int(jnp.sum(state.area >= 0))
    it = 0
    t0 = time.perf_counter()
    with obs.tracer().span("prepare/group",
                           group=-1 if group_index is None else group_index,
                           capacity=capacity) as sp:
        while n_active > 0:
            w = elastic_range(cfg, n_active)
            if it >= max_iters:
                raise RuntimeError(
                    "SubTreePrepare failed to converge after "
                    f"{it} iterations: group={group_index if group_index is not None else '?'} "
                    f"({len(group.prefixes)} prefixes, total_freq={group.total_freq}), "
                    f"w={w}, n_active={n_active}")
            if stats is not None and stats.record_offsets:
                act = np.asarray(state.area) >= 0
                offs = (np.asarray(state.L) + np.asarray(state.start))[act]
                stats.offsets_history.append(offs.astype(np.int64))
            with obs.tracer().span("prepare/step", w=w, n_active=n_active):
                state, n_active_dev = _jit_step(s_padded, state, w,
                                                use_pallas, word_keys)
                if stats is not None:
                    stats.iterations += 1
                    stats.ranges.append(w)
                    stats.active_history.append(n_active)
                    stats.symbols_fetched += n_active * w
                with obs.tracer().span("prepare/readback"):
                    n_active = int(n_active_dev)
            it += 1
        sp.set(iterations=it)
    _record_prepare_metrics([it], time.perf_counter() - t0, cfg)
    return state


def subtree_prepare_batch(
    s_padded,
    groups: list[VirtualTree],
    capacity: int,
    cfg: ElasticConfig = ElasticConfig(),
    stats: PrepareStats | None = None,
    max_iters: int = 10_000,
    sort_fuse: bool | None = None,
    compact: bool | None = None,
) -> PrepareState:
    """Run SubTreePrepare to completion for ALL virtual trees at once.

    The whole working set is one padded (G, F) state driven by a single
    jitted vmapped elastic-range loop: per-group active counts shrink
    independently, converged groups are fixed points (they mask out of the
    gather and the sort leaves them in place), and the state buffers are
    donated so the loop runs in-place on device.  The elastic range is
    shared across the batch, keyed to the busiest group — range choice
    never changes results (Fig. 9b invariant), only I/O.

    ``sort_fuse``/``compact`` default to the promoted engine (fused
    single-lane sort keys + tail compaction, both proven bit-identical in
    the fabric); ``REPRO_SORT=lexsort`` / ``REPRO_COMPACT=off`` — or the
    explicit arguments — pin the oracle paths.

    Returns the final (G, F) state; slice per group/prefix with
    :func:`segments_of`.
    """
    states = init_batch(groups, capacity)
    use_pallas = kops._use_pallas()
    word_keys = kops._use_word_compare()
    if sort_fuse is None:
        sort_fuse = kops._use_sort_fuse()
    if compact is None:
        compact = kops._use_compaction()
    n_active = np.asarray(jnp.sum(states.area >= 0, axis=1))
    group_iters = np.zeros(len(groups), np.int64)
    it = 0
    t0 = time.perf_counter()
    with obs.tracer().span("prepare/batch_loop", groups=len(groups),
                           capacity=capacity) as sp:
        while int(n_active.max()) > 0:
            w = elastic_range(cfg, int(n_active.max()))
            if it >= max_iters:
                live = np.nonzero(n_active > 0)[0]
                detail = "; ".join(
                    f"group {g}: {len(groups[g].prefixes)} prefixes, "
                    f"total_freq={groups[g].total_freq}, n_active={int(n_active[g])}"
                    for g in live[:8])
                raise RuntimeError(
                    f"SubTreePrepare failed to converge after {it} iterations "
                    f"(w={w}, {len(live)}/{len(groups)} groups active): {detail}")
            if stats is not None and stats.record_offsets:
                act = np.asarray(states.area) >= 0
                offs = (np.asarray(states.L) + np.asarray(states.start))[act]
                stats.offsets_history.append(offs.astype(np.int64))
            group_iters += n_active > 0
            f_prime = (compaction_width(int(n_active.max()), capacity)
                       if compact else None)
            # one span per iteration, from the enqueue to the active
            # counts on the host: the read-back blocks until the step ends
            with obs.tracer().span("prepare/step", w=w,
                                   n_active=int(n_active.sum()),
                                   groups_active=int((n_active > 0).sum()),
                                   f_prime=f_prime or capacity):
                if f_prime is not None:
                    states, n_active_dev = _jit_compact_step_batch(
                        s_padded, states, w, use_pallas, word_keys,
                        sort_fuse, f_prime)
                else:
                    states, n_active_dev = _jit_step_batch(
                        s_padded, states, w, use_pallas, word_keys,
                        sort_fuse)
                if stats is not None:
                    total_active = int(n_active.sum())
                    stats.iterations += 1
                    stats.ranges.append(w)
                    stats.active_history.append(total_active)
                    stats.symbols_fetched += total_active * w
                with obs.tracer().span("prepare/readback"):
                    n_active = np.asarray(n_active_dev)
            it += 1
        sp.set(iterations=it)
    _record_prepare_metrics(group_iters.tolist(),
                            time.perf_counter() - t0, cfg)
    return states


@dataclasses.dataclass
class StreamReport:
    """Accounting for one out-of-core streaming build (paper §4.1 scaled
    to device memory): how many chunks the planner cut, how much
    host→device traffic the pipeline moved, and how much of it was hidden
    behind the elastic-range loop of the previous chunk."""

    n_chunks: int = 0
    overlap: bool = True
    groups: int = 0
    iterations: int = 0            # summed over chunk loops
    bytes_copied: int = 0          # host->device state traffic
    copy_s: float = 0.0            # estimated total copy wall time
    copy_hidden_s: float = 0.0     # portion overlapped with compute
    copy_wait_s: float = 0.0       # blocking remainder actually observed
    chunk_iters: list = dataclasses.field(default_factory=list)

    @property
    def overlap_frac(self) -> float:
        """Fraction of host→device transfer hidden behind compute."""
        return self.copy_hidden_s / self.copy_s if self.copy_s > 0 else 0.0


def _state_nbytes(state: PrepareState) -> int:
    return sum(int(np.asarray(a).nbytes) for a in state)


def subtree_prepare_stream(
    s_padded,
    groups: list[VirtualTree],
    capacity: int,
    cfg: ElasticConfig = ElasticConfig(),
    *,
    plan=None,
    device_budget: int | None = None,
    overlap: bool = True,
    stats: PrepareStats | None = None,
    report: StreamReport | None = None,
    max_iters: int = 10_000,
    sort_fuse: bool | None = None,
    compact: bool | None = None,
) -> tuple[PrepareState, StreamReport]:
    """Out-of-core SubTreePrepare: pipeline group chunks through a device
    memory budget with double-buffered host→device copies.

    The planner (:func:`repro.core.iomodel.plan_stream`, or an explicit
    ``plan``) slices the group list into contiguous chunks whose
    double-buffered (G_chunk, F) state fits ``device_budget``.  Each chunk
    runs the same donated elastic-range loop as
    :func:`subtree_prepare_batch`; while chunk k iterates, chunk k+1's
    host-initialized state is ``jax.device_put`` into a standby buffer
    right after the first step is dispatched, so the copy proceeds behind
    the in-flight compute — the construction-side mirror of the serving
    tier hiding pad/pack behind dispatch.  ``overlap=False`` degrades to
    synchronous copy-then-compute (the benchmark baseline).

    The elastic range is keyed per chunk to the chunk's busiest group.
    Range choice never changes results (Fig. 9b invariant), so the final
    arrays are bit-identical to the one-shot batched build; with the
    default budget (``r_budget_symbols >= w_max * F``) the schedule is
    moreover the same constant ``w_max`` both ways.

    Returns ``(state, report)`` where ``state`` is the full host-resident
    (G, F) :class:`PrepareState` (numpy arrays, original group order) and
    ``report`` carries the copy-overlap accounting.
    """
    from repro.core import iomodel

    if not groups:
        raise ValueError("subtree_prepare_stream needs at least one group")
    if plan is None:
        plan = iomodel.plan_stream(len(groups), capacity,
                                   budget_bytes=device_budget,
                                   double_buffer=overlap)
    rep = report if report is not None else StreamReport()
    rep.n_chunks = plan.n_chunks
    rep.overlap = overlap
    rep.groups = len(groups)

    use_pallas = kops._use_pallas()
    word_keys = kops._use_word_compare()
    if sort_fuse is None:
        sort_fuse = kops._use_sort_fuse()
    if compact is None:
        compact = kops._use_compaction()
    g_total = len(groups)
    out = PrepareState(*(np.empty((g_total, capacity), np.int32)
                         for _ in range(6)))
    chunks = list(plan.chunks)
    group_iters = np.zeros(g_total, np.int64)
    copy_rate = None  # bytes/s, calibrated by the chunk-0 synchronous copy
    t0 = time.perf_counter()

    def _copy_sync(host_state: PrepareState) -> PrepareState:
        nonlocal copy_rate
        nb = _state_nbytes(host_state)
        t = time.perf_counter()
        dev = jax.device_put(host_state)
        dev = jax.block_until_ready(dev)
        dt = max(time.perf_counter() - t, 1e-9)
        rep.copy_s += dt
        rep.bytes_copied += nb
        if copy_rate is None:
            copy_rate = nb / dt
        return dev

    with obs.tracer().span("stream/pipeline", chunks=plan.n_chunks,
                           groups=g_total, capacity=capacity,
                           overlap=overlap) as sp_pipe:
        # chunk 0 has no in-flight compute to hide behind: copy it
        # synchronously, which also calibrates the copy-rate estimate
        # used for the prefetched chunks.
        lo0, hi0 = chunks[0]
        states = _copy_sync(_host_init_batch(groups[lo0:hi0], capacity))
        for ci, (lo, hi) in enumerate(chunks):
            nxt = chunks[ci + 1] if ci + 1 < len(chunks) else None
            host_next = (_host_init_batch(groups[nxt[0]:nxt[1]], capacity)
                         if nxt is not None else None)
            standby = None
            t_issue = 0.0
            n_active = np.asarray(jnp.sum(states.area >= 0, axis=1))
            it = 0
            with obs.tracer().span("stream/chunk", chunk=ci,
                                   groups=hi - lo) as sp:
                while int(n_active.max()) > 0:
                    w = elastic_range(cfg, int(n_active.max()))
                    if it >= max_iters:
                        raise RuntimeError(
                            f"SubTreePrepare (stream chunk {ci}, groups "
                            f"[{lo}, {hi})) failed to converge after {it} "
                            f"iterations (w={w})")
                    group_iters[lo:hi] += n_active > 0
                    f_prime = (compaction_width(int(n_active.max()),
                                                capacity)
                               if compact else None)
                    with obs.tracer().span(
                            "prepare/step", w=w,
                            n_active=int(n_active.sum()),
                            groups_active=int((n_active > 0).sum()),
                            f_prime=f_prime or capacity):
                        if f_prime is not None:
                            states, n_active_dev = _jit_compact_step_batch(
                                s_padded, states, w, use_pallas, word_keys,
                                sort_fuse, f_prime)
                        else:
                            states, n_active_dev = _jit_step_batch(
                                s_padded, states, w, use_pallas, word_keys,
                                sort_fuse)
                        if (overlap and standby is None
                                and host_next is not None):
                            # the step above is dispatched asynchronously
                            # — issue the standby copy now so it transfers
                            # behind the chunk's in-flight elastic loop
                            t_issue = time.perf_counter()
                            standby = jax.device_put(host_next)
                        if stats is not None:
                            total_active = int(n_active.sum())
                            stats.iterations += 1
                            stats.ranges.append(w)
                            stats.active_history.append(total_active)
                            stats.symbols_fetched += total_active * w
                        with obs.tracer().span("prepare/readback"):
                            n_active = np.asarray(n_active_dev)
                    it += 1
                sp.set(iterations=it)
            rep.iterations += it
            rep.chunk_iters.append(it)
            # drain this chunk's results to the host output slice (blocks
            # on the chunk's compute, NOT on the standby copy)
            for o, d in zip(out, states):
                o[lo:hi] = np.asarray(d)
            if host_next is None:
                continue
            if not overlap or standby is None:
                # synchronous mode, or a chunk that converged at init
                # (zero iterations -> nothing to hide the copy behind)
                states = _copy_sync(host_next)
                continue
            nb = _state_nbytes(host_next)
            t_wait = time.perf_counter()
            states = jax.block_until_ready(standby)
            wait = time.perf_counter() - t_wait
            est = max(nb / copy_rate, wait)  # >= observed blocking time
            rep.bytes_copied += nb
            rep.copy_s += est
            rep.copy_wait_s += wait
            rep.copy_hidden_s += est - wait
            obs.tracer().complete(
                "stream/standby_copy", int(t_issue * 1e9),
                int(max(time.perf_counter() - t_issue, 1e-9) * 1e9),
                chunk=ci + 1, bytes=nb, wait_ms=round(wait * 1e3, 3),
                hidden_frac=round((est - wait) / est, 4) if est > 0 else 1.0)
        sp_pipe.set(iterations=rep.iterations,
                    copy_ms=round(rep.copy_s * 1e3, 3),
                    hidden_ms=round(rep.copy_hidden_s * 1e3, 3),
                    overlap_frac=round(rep.overlap_frac, 4))
    _record_prepare_metrics(group_iters.tolist(),
                            time.perf_counter() - t0, cfg)
    return out, rep


def segments_of(group: VirtualTree) -> list[tuple[int, int]]:
    """(offset, length) of each prefix's slice in the packed state arrays."""
    segs = []
    off = 0
    for p in group.prefixes:
        segs.append((off, p.freq))
        off += p.freq
    return segs

"""Sharded index fabric: SPMD construction + routed multi-shard serving.

This is ERA's shared-nothing parallel version (paper §7) reborn as a JAX
SPMD program over a device mesh, in two halves:

**Sharded construction** (:func:`sharded_prepare`).  Virtual-tree groups
are embarrassingly parallel, so the batched (G, F) elastic-range loop
shards its G axis: a 1-D ``("shard",)`` mesh, the string replicated
(``P()`` — a dense PackedText replicates ``8/bits``x fewer bytes), the
per-shard ``(G_shard, F)`` state donated in place.  Each ``shard_map``
step wraps the vmapped :func:`repro.core.prepare.prepare_step` in a
``lax.cond`` on the shard's OWN active count — a converged shard's
devices skip the gather/sort/sweep entirely and exit the loop
independently (the per-shard convergence mask) while the host keeps
driving until the globally busiest shard finishes.  The elastic range
``w`` stays keyed to the globally busiest group, exactly the schedule the
single-device engine uses, so results are bit-identical (range choice
never changes results — the Fig. 9b invariant).  The fabric step also
enables the fused sort-key path (``sort_fuse``): the (major, window,
tie) sort triple packs into the fewest uint32 lanes, which is where the
fabric's single-core speedup comes from when the mesh is simulated on
one CPU (see ``benchmarks/bench_fabric.py`` for the attribution).

**ShardedIndex** — the flattened :class:`repro.core.query.DeviceIndex`
leaf arrays sharded by the dense top-trie route key.  Sub-trees sort
lexicographically, so contiguous runs of sub-trees are contiguous route
code intervals; shards cut ONLY between sub-trees whose depth-``k_route``
intervals do not overlap (sub-trees deeper than the routing table share a
cell and must stay together).  Every shard is a self-contained
DeviceIndex (same global ``k_route``, replicated string) placed on its
own mesh device, plus a replicated host-side route→shard table:
``find_batch`` / ``find_fetch_batch`` split each query batch by route
key, run each sub-batch against ONLY its owning shard's
``pattern_probe_words`` descent, and gather just the small verdicts —
no all-gather on the hot path.  Patterns shorter than ``k_route`` may
span a shard boundary; they fan out to every covered shard and the
sorted position lists concatenate associatively, so results stay
bit-identical to the single-device engine.  Per-shard npz archives
(``{path}_shard{k}.npz``) let a multi-host job warm-start each shard
locally.

CPU testing: simulate the mesh with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set BEFORE
importing jax — ``repro.launch.shard_run`` does this for you).
"""

from __future__ import annotations

import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.prepare import (
    ElasticConfig,
    PrepareState,
    PrepareStats,
    compact_step_batch,
    compaction_width,
    elastic_range,
    init_batch,
    prepare_step_batch,
)
from repro.core import packing as packing_mod
from repro.core.query import DeviceIndex, route_depth, shard_npz_path
from repro.kernels import ops as kops

SHARD_AXIS = "shard"


def fabric_mesh(n_shards: int | None = None) -> jax.sharding.Mesh:
    """A 1-D ``("shard",)`` mesh over the first ``n_shards`` devices
    (default: all of them)."""
    devices = jax.devices()
    n = len(devices) if n_shards is None else n_shards
    if not 1 <= n <= len(devices):
        raise ValueError(f"n_shards={n} needs 1..{len(devices)} devices")
    from repro.launch.mesh import make_fabric_mesh
    return make_fabric_mesh(n)


# ---- sharded construction --------------------------------------------------

_STEP_CACHE: dict = {}


def _shard_step(mesh, w: int, use_pallas: bool, word_keys: bool,
                sort_fuse: bool, use_cond: bool, f_prime: int | None):
    """The jitted SPMD elastic step for one ``(w, f_prime)`` bucket.

    Per shard: with ``use_cond``, a ``lax.cond`` on the shard's own
    active count — converged shards are exact fixed points and skip the
    work entirely (their predicate is device-local, so the branch is a
    REAL skip, not a select).  The cond boundary costs ~2ms/step in
    buffer copies, so the host only requests it once some shard has
    actually converged; while every shard is live the cond would take
    the same branch everywhere and the plain step is identical.  With
    ``f_prime``, the step runs compacted — the shared
    :func:`repro.core.prepare.compact_step_batch`, the same path the
    batched/streaming/append drivers now default through.
    State buffers are donated; the string is replicated.
    """
    key = (mesh, w, use_pallas, word_keys, sort_fuse, use_cond, f_prime)
    cached = _STEP_CACHE.get(key)
    if cached is not None:
        return cached

    def one_shard(s_padded, states):
        def live(sts):
            if f_prime is not None:
                new, _ = compact_step_batch(
                    s_padded, sts, f_prime=f_prime, w=w,
                    use_pallas=use_pallas, word_keys=word_keys,
                    sort_fuse=sort_fuse)
            else:
                new, _ = prepare_step_batch(
                    s_padded, sts, w=w, use_pallas=use_pallas,
                    word_keys=word_keys, sort_fuse=sort_fuse)
            return new
        if use_cond:
            states = jax.lax.cond(jnp.sum(states.area >= 0) > 0,
                                  live, lambda sts: sts, states)
        else:
            states = live(states)
        return states, jnp.sum(states.area >= 0, axis=1)

    fn = jax.shard_map(one_shard, mesh=mesh,
                       in_specs=(P(), P(SHARD_AXIS, None)),
                       out_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS)),
                       check_vma=False)
    jitted = jax.jit(fn, donate_argnums=(1,))
    _STEP_CACHE[key] = jitted
    return jitted


def _pad_group_axis(states: PrepareState, g_pad: int) -> PrepareState:
    """Pad the G axis with born-converged dummy groups (area = -1
    everywhere) so it divides evenly across the mesh."""
    g = states.L.shape[0]
    if g_pad == g:
        return states

    def pad(x, fill):
        extra = jnp.full((g_pad - g,) + x.shape[1:], fill, x.dtype)
        return jnp.concatenate([x, extra], axis=0)

    return PrepareState(L=pad(states.L, -1), start=pad(states.start, 0),
                        area=pad(states.area, -1), b_off=pad(states.b_off, -1),
                        b_c1=pad(states.b_c1, 0), b_c2=pad(states.b_c2, 0))


def sharded_prepare(
    s_padded,
    groups,
    capacity: int,
    cfg: ElasticConfig = ElasticConfig(),
    *,
    mesh: jax.sharding.Mesh | None = None,
    stats: PrepareStats | None = None,
    max_iters: int = 10_000,
    sort_fuse: bool | None = None,
    compact: bool | None = None,
) -> PrepareState:
    """:func:`repro.core.prepare.subtree_prepare_batch` over a device
    mesh: groups split into contiguous per-shard blocks, one SPMD step
    per elastic iteration, per-shard convergence mask.  ``sort_fuse`` /
    ``compact`` resolve as in the single-device engine.

    Returns the final (G, F) state (sliced back to the real group count;
    dummy padding groups never reach the caller) — bit-identical to the
    single-device batched engine.
    """
    mesh = mesh or fabric_mesh()
    n_shards = mesh.devices.size
    g = len(groups)
    g_pad = -(-g // n_shards) * n_shards
    use_pallas = kops._use_pallas()
    word_keys = kops._use_word_compare()
    if sort_fuse is None:
        sort_fuse = kops._use_sort_fuse()
    if compact is None:
        compact = kops._use_compaction()

    states = _pad_group_axis(init_batch(groups, capacity), g_pad)
    states = jax.device_put(
        states, NamedSharding(mesh, P(SHARD_AXIS, None)))
    n_active = np.asarray(jnp.sum(states.area >= 0, axis=1))
    it = 0
    t0 = time.perf_counter()
    with obs.tracer().span("fabric/shard_loop", groups=g, shards=n_shards,
                           capacity=capacity) as sp:
        while int(n_active.max()) > 0:
            # the GLOBAL busiest group keys the range — the same schedule
            # (and therefore the same per-iteration states) as the
            # single-device engine; per-shard schedules would also be
            # valid (Fig. 9b) but would break step-for-step comparability
            w = elastic_range(cfg, int(n_active.max()))
            if it >= max_iters:
                raise RuntimeError(
                    f"sharded SubTreePrepare failed to converge after {it} "
                    f"iterations (w={w}, "
                    f"{int((n_active > 0).sum())}/{g} groups active)")
            shards_active = n_active.reshape(n_shards, -1).max(axis=1) > 0
            # tail compaction: once every group's active count fits in
            # half the state width, sort only the active rows (the
            # pow2 bucket keeps program variants to ~log2(F) per w)
            f_prime = (compaction_width(int(n_active.max()), capacity)
                       if compact else None)
            with obs.tracer().span("fabric/step", w=w,
                                   n_active=int(n_active.sum()),
                                   shards_active=int(shards_active.sum()),
                                   f_prime=f_prime or capacity):
                # the convergence mask (lax.cond) only enters the program
                # once a shard has actually converged — before that every
                # shard takes the live branch and the cond boundary is
                # pure copy overhead
                step = _shard_step(mesh, w, use_pallas, word_keys,
                                   sort_fuse,
                                   not bool(shards_active.all()), f_prime)
                states, n_active_dev = step(s_padded, states)
            if stats is not None:
                stats.iterations += 1
                stats.ranges.append(w)
                stats.active_history.append(int(n_active.sum()))
                stats.symbols_fetched += int(n_active.sum()) * w
            n_active = np.asarray(n_active_dev)
            it += 1
        sp.set(iterations=it)
    return PrepareState(*(x[:g] for x in states))


# ---- shard planning --------------------------------------------------------


def _entry_code_intervals(prefixes, base: int, k_route: int):
    """Per sub-tree depth-``k_route`` route-code interval [clo, chi] —
    the same intervals ``DeviceIndex.from_prepare`` routes with."""
    clo = np.zeros(len(prefixes), np.int64)
    chi = np.zeros(len(prefixes), np.int64)
    for t, p in enumerate(prefixes):
        kk = min(len(p), k_route)
        c = 0
        for j in range(kk):
            c = c * base + p[j]
        clo[t] = c * base ** (k_route - kk)
        chi[t] = clo[t] + base ** (k_route - kk) - 1
    return clo, chi


def plan_shards(prefixes, freqs, base: int, k_route: int,
                n_shards: int) -> list[slice]:
    """Split the sorted sub-tree list into ≤ ``n_shards`` contiguous,
    leaf-balanced chunks, cutting ONLY where adjacent route intervals do
    not overlap (sub-trees deeper than ``k_route`` share a cell and must
    stay on one shard).  Returns per-shard entry slices."""
    n = len(prefixes)
    if n_shards < 1:
        raise ValueError(f"n_shards={n_shards} must be >= 1")
    clo, chi = _entry_code_intervals(prefixes, base, k_route)
    # legal cut AFTER entry t: the next entry starts a fresh route cell
    cuts = np.nonzero(chi[:-1] < clo[1:])[0] + 1  # entry indices
    cum = np.concatenate([[0], np.cumsum(np.asarray(freqs, np.int64))])
    total = cum[-1]
    bounds = [0]
    for k in range(1, n_shards):
        target = total * k // n_shards
        if not len(cuts):
            break
        j = int(np.argmin(np.abs(cum[cuts] - target)))
        cut = int(cuts[j])
        if cut > bounds[-1]:
            bounds.append(cut)
            cuts = cuts[cuts > cut]
    bounds.append(n)
    return [slice(a, b) for a, b in zip(bounds[:-1], bounds[1:])]


# ---- the sharded index -----------------------------------------------------


class ShardedIndex:
    """A :class:`DeviceIndex` per route-key shard + the replicated
    route→shard table.  Query results are bit-identical to one
    DeviceIndex over the whole string (pinned by tests/test_fabric.py).
    """

    def __init__(self, shards: list[DeviceIndex], cell_lo: np.ndarray):
        if not shards:
            raise ValueError("ShardedIndex needs at least one shard")
        self.shards = shards
        self.cell_lo = np.asarray(cell_lo, np.int64)  # first owned cell
        dev = shards[0]
        self.base = dev.base
        self.k_route = dev.k_route
        self.max_pattern_len = dev.max_pattern_len
        n_cells = self.base ** self.k_route
        # the replicated route→shard table: every cell's owning shard
        # (cells before shard 0 resolve there and simply miss)
        self.route2shard = (np.searchsorted(
            self.cell_lo, np.arange(n_cells, dtype=np.int64),
            side="right") - 1).clip(0).astype(np.int32)

    # ---- construction ------------------------------------------------------

    @classmethod
    def from_flat(cls, *, alphabet, s, prefixes, freqs, ell,
                  n_shards: int, route_cap: int = 1 << 18,
                  max_pattern_len: int = 512, packing: str = "auto",
                  place: bool | None = None,
                  epoch: int = 0) -> "ShardedIndex":
        """Build from flattened construction output (the same inputs as
        :meth:`DeviceIndex.from_prepare`) split into ≤ ``n_shards``
        route-contiguous shards.  ``place`` distributes shard arrays
        round-robin over the local devices (default: only when there is
        more than one)."""
        freqs = np.asarray(freqs, np.int32)
        max_plen = max(len(p) for p in prefixes)
        k_route = route_depth(alphabet.base, max_plen, route_cap)
        slices = plan_shards(prefixes, freqs, alphabet.base, k_route,
                             n_shards)
        offs = np.concatenate([[0], np.cumsum(freqs)]).astype(np.int64)
        devices = jax.devices()
        if place is None:
            place = len(devices) > 1
        shards, cell_lo = [], []
        ell = jnp.asarray(ell)
        for k, sl in enumerate(slices):
            dev = DeviceIndex.from_prepare(
                alphabet=alphabet, s=s, prefixes=prefixes[sl],
                freqs=freqs[sl], ell=ell[offs[sl.start]:offs[sl.stop]],
                route_cap=route_cap, max_pattern_len=max_pattern_len,
                packing=packing, k_route=k_route, epoch=epoch)
            if place:
                dev = _place_index(dev, devices[k % len(devices)])
            shards.append(dev)
            clo, _ = _entry_code_intervals(prefixes[sl.start:sl.start + 1],
                                           alphabet.base, k_route)
            cell_lo.append(int(clo[0]))
        return cls(shards, np.asarray(cell_lo, np.int64))

    # ---- routing -----------------------------------------------------------

    def route_key(self, pattern):
        """Global cache key (route code, length, bytes) — identical
        across shards because ``k_route`` is shared."""
        return self.shards[0].route_key(pattern)

    def shard_span(self, pattern) -> tuple[int, int]:
        """(lo, hi) inclusive shard range a pattern's route covers.
        Patterns of length >= k_route hit exactly one shard; shorter
        ones cover a cell interval that may cross a boundary."""
        arr = np.asarray(pattern, np.int32)
        kk = min(arr.size, self.k_route)
        c = 0
        for j in range(kk):
            c = c * self.base + int(arr[j])
        span = self.base ** (self.k_route - kk)
        c_lo = c * span
        lo = int(self.route2shard[c_lo])
        hi = int(self.route2shard[c_lo + span - 1])
        return lo, hi

    def _split_batch(self, patterns):
        """shard id → list of pattern indices (fan-out for short spans)."""
        per_shard: dict[int, list[int]] = {}
        for i, p in enumerate(patterns):
            lo, hi = self.shard_span(p)
            for k in range(lo, hi + 1):
                per_shard.setdefault(k, []).append(i)
        return per_shard

    # ---- queries -----------------------------------------------------------

    def find_batch(self, patterns) -> list[np.ndarray]:
        """Per-pattern sorted occurrence positions; each sub-batch runs
        only against its owning shard (route → local probe → verdicts)."""
        out: list = [None] * len(patterns)
        for k, idxs in sorted(self._split_batch(patterns).items()):
            with obs.tracer().span("fabric/find_batch", shard=k,
                                   rows=len(idxs)):
                hits = self.shards[k].find_batch([patterns[i] for i in idxs])
            for i, h in zip(idxs, hits):
                out[i] = h if out[i] is None else np.sort(
                    np.concatenate([out[i], h]))
        return out

    def find_fetch_batch(self, patterns, *, fetch: int = 32):
        """Positions + a (fetch,) context window at the first SA-order
        match.  Shards are route-ordered, so the first shard (ascending)
        with a hit owns the globally first match's window."""
        out: list = [None] * len(patterns)
        wins = np.full((len(patterns), fetch), -1, np.int32)
        filled = [False] * len(patterns)
        for k, idxs in sorted(self._split_batch(patterns).items()):
            with obs.tracer().span("fabric/find_fetch", shard=k,
                                   rows=len(idxs)):
                hits, win = self.shards[k].find_fetch_batch(
                    [patterns[i] for i in idxs], fetch=fetch)
            for j, i in enumerate(idxs):
                out[i] = hits[j] if out[i] is None else np.sort(
                    np.concatenate([out[i], hits[j]]))
                if not filled[i] and len(hits[j]):
                    wins[i] = win[j]
                    filled[i] = True
        return out, wins

    # ---- introspection -----------------------------------------------------

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def n_leaves(self) -> int:
        return sum(int(d.ell.shape[0]) for d in self.shards)

    @property
    def epoch(self) -> int:
        """Mutation generation (uniform across shards — every append
        rebuilds all shards from the merged flat layout)."""
        return self.shards[0].epoch

    def flat_table(self):
        """The global flattened view ``(prefixes, freqs, ell)``.

        Shards are route-ordered and each shard's sub-trees are sorted, so
        concatenating the per-shard tables reproduces EXACTLY the layout
        :meth:`DeviceIndex.from_prepare` flattens — this is what the
        incremental-append merge consumes to reuse unaffected leaf
        segments before re-sharding with :meth:`from_flat`."""
        prefixes: list[tuple] = []
        freq_parts, ell_parts = [], []
        for dev in self.shards:
            plen = np.asarray(dev.sub_plen)
            pref = np.asarray(dev.sub_prefix)
            prefixes += [tuple(int(c) for c in pref[t, :plen[t]])
                         for t in range(len(plen))]
            freq_parts.append(np.asarray(dev.sub_freq))
            ell_parts.append(dev.ell_host)
        return (prefixes, np.concatenate(freq_parts).astype(np.int32),
                np.concatenate(ell_parts).astype(np.int32))

    def string_codes(self) -> np.ndarray:
        # every shard replicates the FULL string in s_text, but a shard's
        # own n_leaves is only its leaf-slice count — |S| is the total
        sh0 = self.shards[0]
        n = self.n_leaves
        if sh0.packed:
            return packing_mod.unpack_text(sh0.s_text, n=n)
        return np.asarray(sh0.s_text)[:n]

    def stats(self) -> dict:
        return {
            "shards": self.n_shards,
            "k_route": self.k_route,
            "leaves": [int(d.ell.shape[0]) for d in self.shards],
            "cell_lo": self.cell_lo.tolist(),
        }

    # ---- persistence -------------------------------------------------------

    def save(self, path: str) -> None:
        """One self-contained npz PER SHARD (``{path}_shard{k}.npz``) so
        each host of a multi-host job warm-starts its shard locally."""
        for k, dev in enumerate(self.shards):
            dev.save(shard_npz_path(path, k))

    @classmethod
    def shard_files(cls, path: str) -> list[str]:
        """The per-shard archives for ``path``, in shard order."""
        pat = shard_npz_path(path, 0).replace("_shard0.npz", "_shard*.npz")
        def shard_no(p):
            m = re.search(r"_shard(\d+)\.npz$", p)
            return int(m.group(1)) if m else -1
        return sorted((p for p in glob.glob(pat) if shard_no(p) >= 0),
                      key=shard_no)

    @classmethod
    def load(cls, path: str) -> "ShardedIndex":
        files = cls.shard_files(path)
        if not files:
            raise FileNotFoundError(f"no shard archives match "
                                    f"{shard_npz_path(path, 0)!r} siblings")
        shards = [DeviceIndex.load(f) for f in files]
        # the route table reconstructs from each shard's first prefix —
        # no separate manifest to keep in sync
        cell_lo = []
        for dev in shards:
            plen = int(np.asarray(dev.sub_plen)[0])
            prefix = tuple(int(c) for c in np.asarray(dev.sub_prefix)[0][:plen])
            clo, _ = _entry_code_intervals([prefix], dev.base, dev.k_route)
            cell_lo.append(int(clo[0]))
        return cls(shards, np.asarray(cell_lo, np.int64))


def _place_index(dev: DeviceIndex, device) -> DeviceIndex:
    """Pin one shard's device arrays to its mesh device (host mirrors
    like ``ell_host`` stay put)."""
    import dataclasses
    put = lambda x: jax.device_put(x, device)
    return dataclasses.replace(
        dev, s_text=put(dev.s_text), ell=put(dev.ell),
        sub_off=put(dev.sub_off), sub_freq=put(dev.sub_freq),
        sub_prefix=put(dev.sub_prefix), sub_plen=put(dev.sub_plen),
        win_lo=put(dev.win_lo), win_hi=put(dev.win_hi),
        pows=put(dev.pows), spans=put(dev.spans))

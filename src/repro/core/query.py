"""Device-resident batched query engine (the read side of the ERA index).

The assembled index (:class:`repro.core.suffix_tree.SuffixTreeIndex`) stores
each sub-tree's leaf array ``L`` as the suffix array restricted to its
prefix.  Because the vertical-partition prefixes are prefix-free and cover
every suffix, concatenating the ``L`` arrays in lexicographic prefix order
yields the full suffix array of ``S`` — so a substring query is a routing
step (which contiguous slice of the concatenation can contain matches?)
plus a bounded lower/upper-bound binary search (paper §2, §4).

:class:`DeviceIndex` flattens the whole index into device arrays:

* ``ell``              — the concatenated leaf arrays (int32[total]);
* ``sub_off/sub_freq`` + padded ``sub_prefix`` — per-subtree tables;
* ``win_lo/win_hi``    — a dense top-trie routing table keyed on packed
  base-|Σ|+1 prefix codes at depth ``k_route`` (capped so the table stays
  small): cell ``c`` maps to the slice of ``ell`` owned by sub-trees whose
  code range touches ``c``.

``find_batch`` then resolves a whole ``(B, m)`` batch of padded patterns
with ONE routing gather and a fixed-trip vectorized binary search whose
inner probe-gather-compare step is the :func:`repro.kernels.ops.pattern_probe`
kernel (Pallas on TPU, pure-jnp oracle elsewhere).  Comparisons run on the
packed big-endian words of :mod:`repro.core.packing` — the same machinery
the construction path sorts with — under unsigned order, so results are
exact for every alphabet including the byte alphabet.

The served string itself (``s_text``) is stored DENSE by default (paper
§6.1 generalized): ``Alphabet.dense_bits`` bits per symbol inside uint32
words (2-bit DNA, 4-bit protein classes) with the byte array as fallback /
reference.  Probe gathers repack in-register to the same byte sort keys,
so results are bit-identical across representations while the serving
index and its per-probe HBM traffic shrink ~``8/bits``x.

The per-pattern numpy path (``SuffixTreeIndex.find``) remains the oracle;
``tests/test_query.py`` / ``tests/test_packed.py`` cross-check the paths
on randomized workloads.
"""

from __future__ import annotations

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing as packing_mod
from repro.kernels import ops as kops


def npz_path(path: str) -> str:
    """The path ``np.savez_compressed`` actually writes (it appends
    ``.npz`` when missing); every save/load here normalizes through this
    so bare paths round-trip."""
    return path if path.endswith(".npz") else path + ".npz"


def shard_npz_path(path: str, k: int) -> str:
    """Per-shard archive path: the ``_shard{k}`` suffix goes BEFORE the
    ``.npz`` extension (``idx.npz`` → ``idx_shard2.npz``), so sharded
    saves never collide with the base archive or each other."""
    base = npz_path(path)
    return f"{base[:-4]}_shard{k}.npz"


def route_depth(base: int, max_plen: int, route_cap: int) -> int:
    """Depth of the dense top-trie routing table: the deepest ``k`` with
    ``base**k`` cells under ``route_cap`` (and within the shallowest
    prefix).  Shared by :meth:`DeviceIndex.from_prepare` and the sharded
    fabric, which must pin ONE global depth across every shard."""
    k_route = 1
    while base ** (k_route + 1) <= route_cap and k_route < max_plen:
        k_route += 1
    return k_route


def _pack_query_batch(s_text, patterns, lengths, word: bool):
    """Pattern packing (once per batch): zero symbols past each length in
    both the pattern and the all-ones mask, so masked suffix words compare
    against exactly the first ``m`` symbols (prefix match == equality).
    Byte path: 0xFF-byte masks over 4-symbol int32 words; word path:
    bits-wide fields over 32/bits-symbol uint32 words."""
    m_pad = patterns.shape[1]
    in_pat = jnp.arange(m_pad, dtype=jnp.int32)[None, :] < lengths[:, None]
    if word:
        bits = s_text.bits
        pat_words = packing_mod.pack_pattern_dense(
            jnp.where(in_pat, patterns, 0), bits, s_text.terminal)
        mask_words = packing_mod.pack_dense(
            jnp.where(in_pat, (1 << bits) - 1, 0), bits)
    else:
        pat_words = packing_mod.pack_words(jnp.where(in_pat, patterns, 0))
        mask_words = packing_mod.pack_words(jnp.where(in_pat, 0xFF, 0))
    return pat_words, mask_words


def _route_window(win_lo, win_hi, pows, spans, lengths, route_syms,
                  k_route: int):
    """Routing: the pattern's depth-k_route code interval [c_lo, c_hi]
    covers every suffix that can match; one gather into the dense table
    bounds the binary search to the owning sub-tree slice of ``ell``."""
    k = jnp.minimum(lengths, k_route)
    in_route = jnp.arange(k_route, dtype=jnp.int32)[None, :] < k[:, None]
    c_lo = jnp.sum(jnp.where(in_route, route_syms, 0) * pows[None, :], axis=1)
    c_hi = c_lo + spans[k]
    lo0 = win_lo[c_lo]
    hi0 = jnp.maximum(win_hi[c_hi], lo0)
    return lo0, hi0


def _search_bounds(s_text, ell, pat_words, mask_words, lengths, lo0, hi0,
                   *, n_iter: int, use_pallas: bool, word: bool):
    """Fixed-trip binary search; lower and upper bound run fused as one
    2B-row probe per iteration (the probe kernel is the only gather).
    Returns (llo, ulo): the lower/upper bound indices into ``ell``."""
    b = pat_words.shape[0]
    total = ell.shape[0]
    if word:
        probe_w = kops.pattern_probe_words_impl(use_pallas)
        len2 = jnp.concatenate([lengths, lengths])
        probe = lambda st, pos, pat, mask: probe_w(st, pos, pat, mask, len2)
    else:
        probe = kops.pattern_probe_impl(use_pallas)

    pat2 = jnp.concatenate([pat_words, pat_words], axis=0)
    mask2 = jnp.concatenate([mask_words, mask_words], axis=0)

    def body(_, st):
        llo, lhi, ulo, uhi = st
        lmid = (llo + lhi) // 2
        umid = (ulo + uhi) // 2
        mids = jnp.concatenate([lmid, umid])
        pos = ell[jnp.clip(mids, 0, total - 1)]
        cmp = probe(s_text, pos, pat2, mask2)
        lcmp, ucmp = cmp[:b], cmp[b:]
        lact = llo < lhi
        uact = ulo < uhi
        # lower bound: first suffix >= pattern (prefix match counts as >=)
        llo = jnp.where(lact & (lcmp < 0), lmid + 1, llo)
        lhi = jnp.where(lact & (lcmp >= 0), lmid, lhi)
        # upper bound: first suffix > pattern
        ulo = jnp.where(uact & (ucmp <= 0), umid + 1, ulo)
        uhi = jnp.where(uact & (ucmp > 0), umid, uhi)
        return llo, lhi, ulo, uhi

    llo, _, ulo, _ = jax.lax.fori_loop(0, n_iter, body, (lo0, hi0, lo0, hi0))
    return llo, ulo


@functools.partial(jax.jit, static_argnames=("k_route", "n_iter", "use_pallas",
                                             "word"))
def _find_batch_ranges(s_text, ell, win_lo, win_hi, pows, spans,
                       patterns, lengths, route_syms,
                       *, k_route: int, n_iter: int, use_pallas: bool,
                       word: bool = False):
    """Route + vectorized lower/upper-bound binary search for one batch.

    s_text: byte string or dense PackedText (the probe dispatches);
    patterns: (B, m_pad) int32, zero-padded; lengths: (B,) int32 >= 1;
    route_syms: (B, k_route) int32 (first symbols, zero-padded).
    ``word`` (PackedText only, real-symbol patterns only) packs the batch
    to k-bit dense words ONCE and runs the word-compare probe — ``bits/8``
    of the pattern key words and compare lanes, identical verdicts.
    Returns (start, count): int32[B] slices into ``ell``.
    """
    pat_words, mask_words = _pack_query_batch(s_text, patterns, lengths, word)
    lo0, hi0 = _route_window(win_lo, win_hi, pows, spans, lengths, route_syms,
                             k_route)
    llo, ulo = _search_bounds(s_text, ell, pat_words, mask_words, lengths,
                              lo0, hi0, n_iter=n_iter, use_pallas=use_pallas,
                              word=word)
    return llo, jnp.maximum(ulo - llo, 0)


def _window_symbols(s_text, win, pos0, fetch: int, word: bool):
    """Decode a fused-gather window back to (B, fetch) int32 symbol codes.

    word rows are ``bits``-bit fields inside uint32 words; byte-key rows
    are 4 big-endian bytes per int32.  Dense storage substitutes
    :func:`repro.core.packing.sub_code` past ``n_real`` on the word path,
    so the true terminal is patched back in by position — making the
    decoded window identical across every representation and oracle leg.
    """
    b = win.shape[0]
    if word:
        bits, spw = s_text.bits, s_text.syms_per_word
        shifts = (32 - bits * (jnp.arange(spw, dtype=jnp.uint32) + 1))
        sym = ((win[:, :, None] >> shifts[None, None, :])
               & ((1 << bits) - 1))
        sym = sym.reshape(b, -1)[:, :fetch].astype(jnp.int32)
    else:
        shifts = jnp.array([24, 16, 8, 0], jnp.int32)
        sym = ((win[:, :, None] >> shifts[None, None, :]) & 0xFF)
        sym = sym.reshape(b, -1)[:, :fetch].astype(jnp.int32)
    if isinstance(s_text, packing_mod.PackedText):
        past = (pos0[:, None] + jnp.arange(fetch, dtype=jnp.int32)[None, :]
                >= s_text.n_real)
        sym = jnp.where(past, jnp.int32(s_text.terminal), sym)
    return sym


@functools.partial(jax.jit, static_argnames=("k_route", "n_iter", "use_pallas",
                                             "word", "fetch"))
def _find_fetch_batch(s_text, ell, win_lo, win_hi, pows, spans,
                      patterns, lengths, route_syms,
                      *, k_route: int, n_iter: int, use_pallas: bool,
                      word: bool, fetch: int):
    """:func:`_find_batch_ranges` plus a fused find-and-fetch epilogue.

    After the search converges, ONE fused probe+gather launch at the
    lower-bound suffix (``ell[start]``) re-verifies the match and returns
    the ``fetch``-symbol text window there — where the two-launch form
    would probe and then gather the same HBM window twice.  Returns
    ``(start, count, window, verified)``: window is (B, fetch) int32
    symbol codes (-1 rows for patterns with no match), ``verified`` the
    fused probe's verdict (0 exactly where count > 0).
    """
    total = ell.shape[0]
    pat_words, mask_words = _pack_query_batch(s_text, patterns, lengths, word)
    lo0, hi0 = _route_window(win_lo, win_hi, pows, spans, lengths, route_syms,
                             k_route)
    llo, ulo = _search_bounds(s_text, ell, pat_words, mask_words, lengths,
                              lo0, hi0, n_iter=n_iter, use_pallas=use_pallas,
                              word=word)
    count = jnp.maximum(ulo - llo, 0)
    pos0 = ell[jnp.clip(llo, 0, total - 1)]
    if word:
        cmp, win = kops.probe_gather_words_impl(use_pallas)(
            s_text, pos0, pat_words, mask_words, lengths, fetch)
    else:
        cmp, win = kops.probe_gather_impl(use_pallas)(
            s_text, pos0, pat_words, mask_words, fetch)
    sym = _window_symbols(s_text, win, pos0, fetch, word)
    sym = jnp.where((count > 0)[:, None], sym, jnp.int32(-1))
    return llo, count, sym, cmp


@dataclasses.dataclass(frozen=True)
class DeviceIndex:
    """Flattened, device-resident form of a :class:`SuffixTreeIndex`."""

    base: int                 # |Σ| + 1 including the terminal
    k_route: int              # routing-trie depth (base**k_route cells)
    n_iter: int               # binary-search trip count (covers ``total``)
    max_pattern_len: int      # padding guarantee baked into ``s_text``
    s_text: object            # the served string: dense PackedText (k-bit
    #                           uint32 words, the default for sub-byte
    #                           alphabets) or uint8[n + pad] terminal-padded
    ell: jax.Array            # int32[total] concatenated leaf arrays (= SA)
    ell_host: np.ndarray      # host copy of ell (result materialization)
    sub_off: jax.Array        # int32[T] slice start of sub-tree t in ell
    sub_freq: jax.Array       # int32[T]
    sub_prefix: jax.Array     # int32[T, max_plen] prefix symbols, -1 pad
    sub_plen: jax.Array       # int32[T]
    win_lo: jax.Array         # int32[base**k_route] routing slice starts
    win_hi: jax.Array         # int32[base**k_route] routing slice ends
    pows: jax.Array           # int32[k_route] base**(k_route-1-j)
    spans: jax.Array          # int32[k_route+1] base**(k_route-k) - 1
    epoch: int = 0            # mutation generation: bumped by incremental
    #                           append; serving flushes RouteCaches on change

    @property
    def n_leaves(self) -> int:
        return int(self.ell.shape[0])

    @property
    def n_subtrees(self) -> int:
        return int(self.sub_off.shape[0])

    @property
    def packed(self) -> bool:
        """True when the string is stored dense (k-bit PackedText)."""
        return isinstance(self.s_text, packing_mod.PackedText)

    @property
    def s_bits(self) -> int:
        """Stored bits per symbol (8 on the byte path)."""
        return self.s_text.bits if self.packed else 8

    @property
    def s_padded(self) -> jax.Array:
        """The terminal-padded byte string (byte-path indexes only; packed
        indexes read through :meth:`read_symbols` / the probe kernels)."""
        if self.packed:
            raise AttributeError(
                "this DeviceIndex stores the string dense-packed; use "
                "s_text / read_symbols / string_codes")
        return self.s_text

    @property
    def string_nbytes(self) -> int:
        """Bytes the served string representation occupies."""
        return (self.s_text.nbytes if self.packed
                else int(self.s_text.shape[0]))

    def read_symbols(self, pos, k: int) -> jax.Array:
        """(B, k) int32 symbol codes starting at each position (device);
        representation-independent (dense storage decodes in-register)."""
        pos = jnp.asarray(pos, jnp.int32)
        if self.packed:
            return packing_mod.gather_symbols_dense(self.s_text, pos, k)
        idx = pos[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
        idx = jnp.minimum(idx, self.s_text.shape[0] - 1)
        return jnp.take(self.s_text, idx, axis=0).astype(jnp.int32)

    def string_codes(self) -> np.ndarray:
        """The indexed string back as uint8 codes (terminal included) —
        ``n_leaves`` symbols, whatever the storage representation."""
        if self.packed:
            return packing_mod.unpack_text(self.s_text, n=self.n_leaves)
        return np.asarray(self.s_text)[: self.n_leaves]

    # ---- construction -----------------------------------------------------

    @classmethod
    def from_index(cls, index, *, route_cap: int = 1 << 18,
                   max_pattern_len: int = 512,
                   packing: str = "auto") -> "DeviceIndex":
        """Flatten ``index`` (a SuffixTreeIndex) into device arrays.

        ``route_cap`` bounds the dense routing table (cells <= route_cap);
        ``max_pattern_len`` fixes how far past |S| gathers may read;
        ``packing`` picks the served string representation
        (auto | dense | bytes — ``auto`` stores DNA at 2 and protein
        classes at 4 bits per symbol).
        """
        prefixes = sorted(index.subtrees)
        if not prefixes:
            raise ValueError("cannot flatten an empty index")
        subs = [index.subtrees[p] for p in prefixes]
        freqs = np.array([st.freq for st in subs], np.int32)
        ell = np.concatenate([np.asarray(st.ell, np.int32) for st in subs])
        return cls.from_prepare(alphabet=index.alphabet, s=np.asarray(index.s),
                                prefixes=prefixes, freqs=freqs, ell=ell,
                                route_cap=route_cap,
                                max_pattern_len=max_pattern_len,
                                packing=packing)

    @classmethod
    def from_prepare(cls, *, alphabet, s: np.ndarray, prefixes, freqs,
                     ell, route_cap: int = 1 << 18,
                     max_pattern_len: int = 512,
                     packing: str = "auto",
                     k_route: int | None = None,
                     epoch: int = 0) -> "DeviceIndex":
        """Assemble directly from construction output — no SubTree dict.

        ``prefixes``: sorted (lexicographic) prefix tuples; ``freqs``: the
        aligned leaf counts; ``ell``: the concatenated leaf arrays in the
        same order (a device array from the batched engine stays on device;
        only the routing tables are computed host-side from the prefix
        metadata).  This is the ``EraIndexer.build_device`` fast path.

        ``k_route`` overrides the routing-table depth: the sharded fabric
        builds one DeviceIndex per shard over a SUBSET of the sub-trees
        and every shard must share the GLOBAL depth (else route codes
        would not be comparable across shards).
        """
        base = alphabet.base
        if not prefixes:
            raise ValueError("cannot flatten an empty index")
        freqs = np.asarray(freqs, np.int32)
        offs = np.concatenate([[0], np.cumsum(freqs)[:-1]]).astype(np.int32)
        total = int(freqs.sum())

        max_plen = max(len(p) for p in prefixes)
        plen = np.array([len(p) for p in prefixes], np.int32)
        pref = np.full((len(prefixes), max_plen), -1, np.int32)
        for t, p in enumerate(prefixes):
            pref[t, : len(p)] = p

        if k_route is None:
            k_route = route_depth(base, max_plen, route_cap)
        n_cells = base**k_route

        # each sub-tree owns the depth-k_route code interval [clo, chi] of
        # its (truncated) prefix; prefix-freeness makes the intervals sorted
        # and non-overlapping (equal only for sub-trees deeper than k_route).
        clo = np.zeros(len(prefixes), np.int64)
        chi = np.zeros(len(prefixes), np.int64)
        for t, p in enumerate(prefixes):
            kk = min(len(p), k_route)
            c = 0
            for j in range(kk):
                c = c * base + p[j]
            clo[t] = c * base ** (k_route - kk)
            chi[t] = clo[t] + base ** (k_route - kk) - 1
        codes = np.arange(n_cells, dtype=np.int64)
        off_ext = np.concatenate([offs, [total]]).astype(np.int32)
        win_lo = off_ext[np.searchsorted(chi, codes, side="left")]
        t_last = np.searchsorted(clo, codes, side="right") - 1
        win_hi = np.where(t_last >= 0, offs[np.maximum(t_last, 0)]
                          + freqs[np.maximum(t_last, 0)], 0).astype(np.int32)

        n_iter = int(np.ceil(np.log2(total + 1))) + 1
        pows = (base ** np.arange(k_route - 1, -1, -1)).astype(np.int32)
        spans = (base ** (k_route - np.arange(k_route + 1)) - 1).astype(np.int32)
        if packing_mod.resolve_dense(packing, alphabet):
            s_text = packing_mod.pack_text(np.asarray(s), alphabet,
                                           extra=max_pattern_len + 8)
        else:
            s_text = jnp.asarray(alphabet.pad_string(s, extra=max_pattern_len + 8))
        return cls(
            base=base,
            k_route=k_route,
            n_iter=n_iter,
            max_pattern_len=max_pattern_len,
            s_text=s_text,
            ell=jnp.asarray(ell),  # no-op for a device array from the batched engine
            ell_host=np.asarray(ell),
            sub_off=jnp.asarray(offs),
            sub_freq=jnp.asarray(freqs),
            sub_prefix=jnp.asarray(pref),
            sub_plen=jnp.asarray(plen),
            win_lo=jnp.asarray(win_lo),
            win_hi=jnp.asarray(win_hi),
            pows=jnp.asarray(pows),
            spans=jnp.asarray(spans),
            epoch=int(epoch),
        )

    # ---- persistence ------------------------------------------------------
    # The flattened form round-trips through npz so serving drivers
    # (query_serve / analytics_serve) can start without re-building and
    # re-flattening the index.  AnalyticsEngine reuses the blob helpers to
    # store its LCP array alongside the same fields in one file.
    #
    # Two string encodings coexist: byte-path saves keep the ORIGINAL
    # 4-entry-meta + ``s_padded`` layout (so pre-packing archives load
    # unchanged and byte saves stay readable by older code); dense saves
    # write ``s_words`` (uint32) and extend ``meta`` with
    # ``[s_bits, n_real]``.  The mutation ``epoch`` rides as ONE trailing
    # meta entry on both layouts — archives written before the append era
    # are shorter and load as epoch 0.

    _BLOB_FIELDS = ("ell", "sub_off", "sub_freq", "sub_prefix",
                    "sub_plen", "win_lo", "win_hi", "pows", "spans")

    def to_blobs(self) -> dict[str, np.ndarray]:
        meta = [self.base, self.k_route, self.n_iter, self.max_pattern_len]
        if self.packed:
            meta += [self.s_text.bits, int(self.s_text.n_real)]
            blobs = {"s_words": np.asarray(self.s_text.words)}
        else:
            blobs = {"s_padded": np.asarray(self.s_text)}
        meta.append(self.epoch)
        blobs["meta"] = np.array(meta, np.int64)
        for name in self._BLOB_FIELDS:
            blobs[name] = np.asarray(getattr(self, name))
        return blobs

    @classmethod
    def from_blobs(cls, data) -> "DeviceIndex":
        meta = np.asarray(data["meta"])
        ell = np.asarray(data["ell"], np.int32)
        if "s_words" in data:
            s_text = packing_mod.PackedText(
                words=jnp.asarray(np.asarray(data["s_words"], np.uint32)),
                n_real=jnp.asarray(int(meta[5]), jnp.int32),
                bits=int(meta[4]), terminal=int(meta[0]) - 1)
            epoch = int(meta[6]) if meta.size > 6 else 0
        else:  # byte-format archive (including every pre-packing save)
            s_text = jnp.asarray(data["s_padded"])
            epoch = int(meta[4]) if meta.size > 4 else 0
        fields = {name: jnp.asarray(data[name]) for name in cls._BLOB_FIELDS}
        return cls(base=int(meta[0]), k_route=int(meta[1]), n_iter=int(meta[2]),
                   max_pattern_len=int(meta[3]), s_text=s_text, ell_host=ell,
                   epoch=epoch, **fields)

    def save(self, path: str) -> None:
        """Persist the flattened index (npz); ``load`` restores it exactly."""
        np.savez_compressed(npz_path(path), **self.to_blobs())

    @classmethod
    def load(cls, path: str) -> "DeviceIndex":
        with np.load(npz_path(path)) as data:
            return cls.from_blobs(data)

    # ---- queries ----------------------------------------------------------

    def pad_batch(self, patterns, *, m_pad: int | None = None,
                  b_pad: int | None = None,
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Pad a list of 1-D code arrays to (B, m_pad) + lengths + route rows.

        ``m_pad`` / ``b_pad`` optionally pin the padded width / batch rows
        to caller-chosen bucket sizes (the serving loop buckets both to
        powers of two so recompiles stay bounded); width must be a
        multiple of 4 and at least the natural padded width.  Dummy rows
        (length 1, code 0) fill the batch out to ``b_pad`` — callers slice
        results back to the real row count."""
        if not len(patterns):
            raise ValueError("empty batch")
        lengths = np.array([len(p) for p in patterns], np.int32)
        if (lengths < 1).any():
            raise ValueError("patterns must have length >= 1")
        m_max = int(lengths.max())
        m_nat = -(-m_max // 4) * 4
        if m_pad is None:
            m_pad = m_nat
        elif m_pad % 4 or m_pad < m_nat:
            raise ValueError(
                f"m_pad={m_pad} must be a multiple of 4 and >= {m_nat}")
        if m_pad > self.max_pattern_len:
            raise ValueError(
                f"pattern length {m_max} exceeds max_pattern_len="
                f"{self.max_pattern_len}; rebuild with to_device(max_pattern_len=...)")
        b = len(patterns)
        if b_pad is None:
            b_pad = b
        elif b_pad < b:
            raise ValueError(f"b_pad={b_pad} < batch size {b}")
        padded = np.zeros((b_pad, m_pad), np.int32)
        route = np.zeros((b_pad, self.k_route), np.int32)
        for i, p in enumerate(patterns):
            arr = np.asarray(p, np.int32)
            if arr.size and (arr.min() < 0 or arr.max() >= self.base):
                raise ValueError(f"pattern {i} has codes outside [0, {self.base})")
            padded[i, : len(arr)] = arr
            route[i, : min(len(arr), self.k_route)] = arr[: self.k_route]
        if b_pad > b:
            lengths = np.concatenate(
                [lengths, np.ones(b_pad - b, np.int32)])
        return padded, lengths, route

    def _word_gate(self, patterns, pat_max: int | None) -> bool:
        """Resolve the word-vs-byte probe gate (a STATIC jit arg).

        A batch carrying the terminal sentinel as a pattern code
        (degenerate but accepted) must fall back to the byte-key probe,
        whose verdicts are defined for it.  The max code must reach the
        host; serving passes the ``pat_max`` it already tracks at
        admission so device-resident batches avoid even the one scalar
        sync of the device reduce."""
        if not (self.packed and kops._use_word_compare()):
            return False
        if pat_max is None:
            if isinstance(patterns, jax.Array):
                pat_max = int(jnp.max(patterns, initial=0))
            else:
                pat_max = int(np.asarray(patterns).max(initial=0))
        return pat_max < self.s_text.terminal

    def find_batch_ranges(self, patterns, lengths, route_syms,
                          *, pat_max: int | None = None):
        """Jitted core: (B, m_pad)/(B,)/(B, k_route) → (start, count) slices
        of ``ell`` (device arrays; matches are ``ell[start:start+count]``).

        Dense-packed indexes default to the word-compare probe
        (``REPRO_WORD_COMPARE``); batches carrying the terminal sentinel
        fall back to the byte-key probe (see :meth:`_word_gate` — pass
        the already-known ``pat_max`` to keep the call sync-free)."""
        return _find_batch_ranges(
            self.s_text, self.ell, self.win_lo, self.win_hi,
            self.pows, self.spans,
            jnp.asarray(patterns, jnp.int32), jnp.asarray(lengths, jnp.int32),
            jnp.asarray(route_syms, jnp.int32),
            k_route=self.k_route, n_iter=self.n_iter,
            use_pallas=kops._use_pallas(),
            word=self._word_gate(patterns, pat_max),
        )

    def find_fetch_ranges(self, patterns, lengths, route_syms, *, fetch: int,
                          pat_max: int | None = None):
        """Find-and-fetch: :meth:`find_batch_ranges` plus the text window.

        One extra FUSED probe+gather launch (:mod:`repro.kernels.probe_gather`)
        at the lower-bound suffix returns ``fetch`` symbols of context per
        match.  Returns device arrays ``(start, count, window, verified)``;
        ``window`` is (B, fetch) int32 codes (-1 rows where count == 0),
        ``verified`` the fused probe's verdict (0 wherever count > 0).
        """
        if fetch % 4 or fetch <= 0:
            raise ValueError(f"fetch={fetch} must be a positive multiple of 4")
        if fetch > self.max_pattern_len:
            raise ValueError(
                f"fetch={fetch} exceeds max_pattern_len={self.max_pattern_len}"
                " (the gather-past-|S| padding guarantee)")
        return _find_fetch_batch(
            self.s_text, self.ell, self.win_lo, self.win_hi,
            self.pows, self.spans,
            jnp.asarray(patterns, jnp.int32), jnp.asarray(lengths, jnp.int32),
            jnp.asarray(route_syms, jnp.int32),
            k_route=self.k_route, n_iter=self.n_iter,
            use_pallas=kops._use_pallas(),
            word=self._word_gate(patterns, pat_max), fetch=fetch,
        )

    def find_batch(self, patterns) -> list[np.ndarray]:
        """All occurrence positions for each pattern (sorted, int64) —
        the batched device analogue of ``SuffixTreeIndex.find``."""
        padded, lengths, route = self.pad_batch(patterns)
        start, count = self.find_batch_ranges(padded, lengths, route)
        start = np.asarray(start)
        count = np.asarray(count)
        ell = self.ell_host  # avoid a full device->host copy per batch
        return [np.sort(ell[s : s + c].astype(np.int64))
                for s, c in zip(start, count)]

    def find_fetch_batch(self, patterns, *, fetch: int = 32):
        """Host-convenience find-and-fetch over a list of code arrays.

        Returns ``(ranges, windows)``: ``ranges`` the list of sorted
        occurrence-position arrays (as :meth:`find_batch`), ``windows`` a
        (B, fetch) int32 array of text context at the first (SA-order)
        match of each pattern, -1 rows for patterns with no match."""
        padded, lengths, route = self.pad_batch(patterns)
        start, count, win, _ = self.find_fetch_ranges(padded, lengths, route,
                                                      fetch=fetch)
        start = np.asarray(start)
        count = np.asarray(count)
        ell = self.ell_host
        ranges = [np.sort(ell[s : s + c].astype(np.int64))
                  for s, c in zip(start, count)]
        return ranges, np.asarray(win)

    # ---- hot-prefix route cache -------------------------------------------

    def route_key(self, pattern) -> tuple[int, int, bytes]:
        """Cache key for one pattern: (top-trie route code, length, bytes).

        The leading component is the dense depth-``k_route`` route code
        ``c_lo`` — the same cell :func:`_route_window` gathers — so keys
        cluster by the top-trie route the query would take and cache
        introspection can report per-route hit concentrations.  The full
        pattern bytes keep lookups exact: a hit returns (start, count)
        bounds that are byte-identical to running the search, because
        probe verdicts do not depend on the batch's padded width."""
        arr = np.asarray(pattern, np.int32)
        kk = min(arr.size, self.k_route)
        c = 0
        for j in range(kk):
            c = c * self.base + int(arr[j])
        c *= self.base ** (self.k_route - kk)
        return c, arr.size, arr.astype(np.int32).tobytes()

    def find_batch_cached(self, patterns, cache: "RouteCache") -> list[np.ndarray]:
        """:meth:`find_batch` through a :class:`RouteCache`.

        Hits resolve to their memoized (start, count) without touching the
        device; misses run as ONE smaller batch and populate the cache.
        Results are byte-identical to :meth:`find_batch` (exact-pattern
        keys; see :meth:`route_key`)."""
        keys = [self.route_key(p) for p in patterns]
        bounds: list[tuple[int, int] | None] = [cache.get(k) for k in keys]
        # dedupe misses by key: a hot pattern repeated inside one batch
        # costs one search row, and every repeat resolves from that row
        miss: dict[tuple, int] = {}
        for i, bnd in enumerate(bounds):
            if bnd is None and keys[i] not in miss:
                miss[keys[i]] = i
        if miss:
            padded, lengths, route = self.pad_batch(
                [patterns[i] for i in miss.values()])
            start, count = self.find_batch_ranges(padded, lengths, route)
            start = np.asarray(start)
            count = np.asarray(count)
            solved = {k: (int(start[j]), int(count[j]))
                      for j, k in enumerate(miss)}
            for k, bnd in solved.items():
                cache.put(k, bnd)
            for i, bnd in enumerate(bounds):
                if bnd is None:
                    bounds[i] = solved[keys[i]]
        ell = self.ell_host
        return [np.sort(ell[s : s + c].astype(np.int64))
                for s, c in bounds]


# One fifth of a RouteCache is probation: keys seen once cycle through it
# alone, so a stream of one-off patterns cannot flush the recurring ones.
_PROBATION_SHARE = 5


class RouteCache:
    """Segmented-LRU memo of (route-keyed pattern → cached answer).

    Keyed by :meth:`DeviceIndex.route_key` — exact pattern identity under a
    top-trie route prefix — so the head of a skewed query distribution
    skips the whole binary-search descent; the memoized bounds are exactly
    what the search returns (verdicts are padded-width-independent), which
    is what makes cache-on/off serving byte-identical.

    Scan-resistant: a ``put`` enters a *probation* segment; a ``get`` that
    hits there promotes the entry to a *protected* segment of at most
    ``capacity - max(1, capacity // 5)`` entries, whose overflow is demoted
    (not evicted) back to probation's MRU end.  Probation's LRU entry is
    evicted first, so keys seen once displace only each other and the
    answers that recur stay.  ``capacity`` bounds both segments together.
    Hit/miss/eviction/promotion/demotion counters feed the serving
    driver's stats."""

    def __init__(self, capacity: int = 4096):
        if capacity < 0:
            raise ValueError(f"capacity={capacity} must be >= 0")
        self.capacity = capacity
        self.protected_capacity = (
            capacity - max(1, capacity // _PROBATION_SHARE) if capacity else 0)
        self._probation: collections.OrderedDict[tuple, tuple] = \
            collections.OrderedDict()
        self._protected: collections.OrderedDict[tuple, tuple] = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.promotions = 0
        self.demotions = 0

    def __len__(self) -> int:
        return len(self._probation) + len(self._protected)

    def get(self, key) -> tuple | None:
        got = self._protected.get(key)
        if got is not None:
            self._protected.move_to_end(key)
            self.hits += 1
            return got
        got = self._probation.get(key)
        if got is None:
            self.misses += 1
            return None
        self.hits += 1
        if not self.protected_capacity:  # capacity 1: probation is all
            self._probation.move_to_end(key)
            return got
        del self._probation[key]
        self._protected[key] = got
        self.promotions += 1
        if len(self._protected) > self.protected_capacity:
            old, val = self._protected.popitem(last=False)
            self._probation[old] = val
            self.demotions += 1
        return got

    def put(self, key, value: tuple) -> None:
        if self.capacity == 0:
            return
        seg = self._protected if key in self._protected else self._probation
        seg[key] = value
        seg.move_to_end(key)
        while len(self) > self.capacity:
            (self._probation or self._protected).popitem(last=False)
            self.evictions += 1

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def stats(self) -> dict:
        return {"size": len(self), "capacity": self.capacity,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "hit_rate": self.hit_rate,
                "promotions": self.promotions, "demotions": self.demotions,
                "protected": len(self._protected)}

    def clear(self) -> None:
        self._probation.clear()
        self._protected.clear()

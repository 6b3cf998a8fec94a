"""Shared packed-word machinery for construction AND querying.

Two representations live here:

**Sort keys** — one byte per symbol code, packed big-endian
4-symbols/int32 so that the UNSIGNED integer order of the packed words
equals the lexicographic order of the symbol sequence.  This is the single
comparison currency of the whole pipeline:

* :mod:`repro.core.prepare`  — elastic-range sort keys (SubTreePrepare),
* :mod:`repro.core.build`    — clz-based log2 in the parallel builder,
* :mod:`repro.core.query`    — batched pattern/suffix comparisons,
* :mod:`repro.kernels.ref`   — the pure-jnp kernel oracles.

**Storage** (:class:`PackedText`) — the string itself held DENSE at
``Alphabet.dense_bits`` bits per symbol (paper §6.1 generalized beyond
DNA: 2-bit DNA, 4-bit reduced-protein classes, 8-bit fallback), big-endian
inside uint32 words.  Gathers read the dense words and REPACK in-register
into the exact byte-per-symbol sort keys above (:func:`gather_pack_dense`),
so every downstream lexsort / LCP / probe is bit-identical between the
dense and byte paths while HBM string traffic shrinks by ``8/bits``.  The
terminal is *virtual* in dense storage: it only ever occurs at the end of
the string, so a gather substitutes the terminal code for every position
``>= n_real`` instead of spending a code point on it (codes ``0..|Σ|-1``
must fit ``bits``; the terminal ``|Σ|`` need not).

Signedness: codes up to 127 keep every packed key word non-negative, so
signed int32 comparisons coincide with lexicographic order (the original
DNA / protein assumption).  The byte alphabet (codes up to 255) sets the
int32 sign bit via the top byte; every sort or comparison on packed key
words must therefore run on the uint32 bit pattern — use :func:`as_u32`
(bitcast) or :func:`flip_sign` (order-preserving int32 remap) at the
comparison site.

**Word comparison** — the packed words themselves are ALSO a comparison
currency (ERA §6.1 taken to its conclusion: 16 DNA symbols per uint32
compare instead of 4 byte-codes per int32).  The subtlety is the virtual
terminal: a bits-saturated alphabet (DNA: 4 codes fill 2 bits exactly)
has no spare bit pattern for ``$``, so dense word reads SUBSTITUTE the
largest representable code (:func:`sub_code`) for every position past
``n_real`` and carry a per-row *limit* — the symbol index of the first
terminal (``n_real - off``).  Every word-level comparison then follows
one rule set, exact for all four alphabets:

* first difference ``p`` (XOR + count-leading-zeros, :func:`lcp_words`)
  below both limits → a real symbol difference, sign/LCP taken directly;
* otherwise the side whose limit comes first holds ``$`` there — it is
  LARGER (the terminal is the largest code) and the LCP is the smaller
  limit (:func:`lcp_words_limited`, :func:`probe_words_ref` in
  ``kernels.ref``);
* rows equal through the window with both limits beyond it are equal —
  the elastic-range sort appends ``w - limit`` as a least-significant
  tiebreak key so equal substituted keys order exactly like the byte
  keys (:func:`word_sort_keys`).

When the terminal fits ``bits`` (4-bit protein classes, 8-bit byte) the
substitution is the identity and the limit rules reduce to no-ops, so one
code path serves every alphabet.  The byte-key path remains the oracle:
both paths emit bit-identical construction arrays, query results and
analytics (``tests/test_packed.py``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

PACK_WEIGHTS = (1 << 24, 1 << 16, 1 << 8, 1)

_SIGN = jnp.int32(-(1 << 31))


def pack_words(sym: jax.Array) -> jax.Array:
    """(…, w) symbol codes → (…, w//4) int32 big-endian packed words."""
    *lead, w = sym.shape
    assert w % 4 == 0, "pack width must be a multiple of 4"
    grp = sym.astype(jnp.int32).reshape(*lead, w // 4, 4)
    weights = jnp.asarray(PACK_WEIGHTS, jnp.int32)
    return jnp.sum(grp * weights, axis=-1)


def gather_pack(s_padded: jax.Array, offs: jax.Array, w: int) -> jax.Array:
    """Gather ``w`` symbols at each offset and pack; pure-jnp fallback path.

    The TPU path is ``repro.kernels.range_gather`` (scalar-prefetch paged
    gather); this fallback is used on CPU and as the kernel oracle.
    """
    idx = offs[:, None].astype(jnp.int32) + jnp.arange(w, dtype=jnp.int32)[None, :]
    # S must be pre-padded with the terminal code (Alphabet.pad_string);
    # clip is only a safety net for the final over-reads of resolved areas.
    idx = jnp.minimum(idx, s_padded.shape[0] - 1)
    sym = jnp.take(s_padded, idx, axis=0)
    return pack_words(sym)


def as_u32(words: jax.Array) -> jax.Array:
    """Bitcast packed int32 words to uint32 (unsigned sort/compare keys)."""
    if words.dtype == jnp.uint32:
        return words
    return jax.lax.bitcast_convert_type(words.astype(jnp.int32), jnp.uint32)


def flip_sign(words: jax.Array) -> jax.Array:
    """XOR the sign bit: signed int32 order of the result == unsigned
    order of the input.  Usable inside Pallas kernels (no bitcast)."""
    return words ^ _SIGN


def clz32(x: jax.Array) -> jax.Array:
    """Count leading zeros of an int32 OR uint32 via bit smear + popcount.

    int32's arithmetic right shifts only over-smear below the highest set
    bit, so the result is exact for negative inputs too (clz == 0);
    uint32's logical shifts are the textbook form.  Plain jnp ops, so it
    is usable inside Pallas kernel bodies."""
    x = x | (x >> 1)
    x = x | (x >> 2)
    x = x | (x >> 4)
    x = x | (x >> 8)
    x = x | (x >> 16)
    return 32 - jax.lax.population_count(x.astype(jnp.uint32)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Dense k-bit text storage (paper §6.1, generalized to the alphabet)
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PackedText:
    """The string stored dense at ``bits`` bits/symbol in uint32 words.

    ``words[k]`` holds symbols ``k*spw .. k*spw + spw - 1`` big-endian
    (``spw = 32 // bits``), so the bit pattern of a word run IS the
    lexicographic order of the symbols it covers.  Only the ``n_real``
    REAL symbols are stored; the terminal (and the terminal padding past
    it) is virtual — readers substitute ``terminal`` for every position
    ``>= n_real``.  ``words`` carries enough zero tail that any gather a
    caller is contracted to make (``n_real + extra`` symbols, see
    :func:`pack_text`) stays in bounds.

    Registered as a pytree with ``bits``/``terminal`` static, so a
    PackedText flows through ``jax.jit`` boundaries and abstract
    ``ShapeDtypeStruct`` lowering (the dry-run) like any array.
    """

    words: jax.Array   # uint32[n_words]; big-endian ``bits``-bit symbols
    n_real: jax.Array  # int32 scalar: symbols stored before the terminal
    bits: int          # static: 2 | 4 | 8
    terminal: int      # static: the (virtual) terminal code

    def tree_flatten(self):
        return (self.words, self.n_real), (self.bits, self.terminal)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(words=children[0], n_real=children[1],
                   bits=aux[0], terminal=aux[1])

    @property
    def syms_per_word(self) -> int:
        return 32 // self.bits

    @property
    def nbytes(self) -> int:
        return int(self.words.shape[0]) * 4


def resolve_dense(mode: str, alphabet) -> bool:
    """Does packing ``mode`` select dense storage for ``alphabet``?

    ``auto`` goes dense exactly when density buys traffic (< 8 bits);
    ``dense`` forces the packed machinery even at 8 bits (byte-equivalent
    density, useful for exercising the generic path); ``bytes`` never."""
    if mode == "bytes":
        return False
    if mode == "dense":
        return True
    if mode == "auto":
        return alphabet.dense_bits < 8
    raise ValueError(f"unknown packing mode {mode!r}; "
                     "choose 'auto', 'dense' or 'bytes'")


def pack_text(codes: np.ndarray, alphabet, *, extra: int = 8) -> PackedText:
    """Dense-pack a TERMINATED code string for device-resident gathers.

    ``codes``: uint8 codes whose last element is the terminal (the form
    :meth:`Alphabet.encode` produces).  ``extra``: how many symbols past
    the end gathers may read (the same contract as
    :meth:`Alphabet.pad_string`) — the word tail is sized to cover it plus
    one halo word for sub-word shift alignment.
    """
    codes = np.asarray(codes, np.uint8)
    if codes.size == 0 or codes[-1] != alphabet.terminal_code:
        raise ValueError("pack_text needs a terminated code string")
    bits = alphabet.dense_bits
    n_real = codes.size - 1
    real = codes[:n_real].astype(np.uint32)
    if real.size and real.max() >= (1 << bits):
        raise ValueError(
            f"codes exceed {bits}-bit dense range for alphabet "
            f"{alphabet.name!r} (max code {int(real.max())})")
    spw = 32 // bits
    n_words = -(-(n_real + extra) // spw) + 1  # +1 halo for shift alignment
    grp = np.zeros(n_words * spw, np.uint32)
    grp[:n_real] = real
    shifts = (32 - bits * (np.arange(spw, dtype=np.uint32) + 1))
    words = (grp.reshape(n_words, spw) << shifts[None, :]).sum(
        axis=1, dtype=np.uint32)
    return PackedText(words=jnp.asarray(words),
                      n_real=jnp.asarray(n_real, jnp.int32),
                      bits=bits, terminal=alphabet.terminal_code)


def pack_text_stream(chunks, alphabet, *, extra: int = 8) -> PackedText:
    """Dense-pack a terminated code string delivered in CHUNKS.

    ``chunks`` is any iterable of uint8 code arrays whose concatenation is
    a terminated code string (the :func:`pack_text` input contract); the
    chunks may have arbitrary sizes and are consumed one at a time, so the
    peak host footprint is one chunk plus a ``< syms_per_word`` carry —
    this is what lets :mod:`repro.launch.warmstart` migrate legacy byte
    archives to dense storage without materializing the decoded string.

    Bit-identical to ``pack_text`` on the concatenated string: symbols are
    committed to words only on ``syms_per_word``-aligned boundaries, the
    final symbol of the stream is held back one step (it must be the
    terminal, which is virtual and never stored), and the zero tail is
    sized by the same ``n_real + extra`` formula.
    """
    bits = alphabet.dense_bits
    spw = 32 // bits
    shifts = (32 - bits * (np.arange(spw, dtype=np.uint32) + 1))
    word_parts: list[np.ndarray] = []
    carry = np.zeros(0, np.uint32)   # committed symbols short of a word
    pending = None                   # last symbol seen; terminal candidate
    n_real = 0

    def commit(sym: np.ndarray) -> None:
        nonlocal carry, n_real
        if sym.size and sym.max() >= (1 << bits):
            raise ValueError(
                f"codes exceed {bits}-bit dense range for alphabet "
                f"{alphabet.name!r} (max code {int(sym.max())})")
        n_real += sym.size
        buf = np.concatenate([carry, sym]) if carry.size else sym
        n_full = buf.size // spw
        if n_full:
            head = buf[:n_full * spw].reshape(n_full, spw)
            word_parts.append(
                (head << shifts[None, :]).sum(axis=1, dtype=np.uint32))
        carry = buf[n_full * spw:]

    for chunk in chunks:
        c = np.asarray(chunk, np.uint8).astype(np.uint32)
        if c.size == 0:
            continue
        if pending is not None:
            c = np.concatenate([[pending], c])
        pending = int(c[-1])
        commit(c[:-1])
    if pending is None or pending != alphabet.terminal_code:
        raise ValueError("pack_text_stream needs a terminated code string")

    n_words = -(-(n_real + extra) // spw) + 1  # same formula as pack_text
    tail = np.zeros(n_words * spw - n_real, np.uint32)
    commit_real = n_real                       # commit() would double-count
    commit(tail)
    n_real = commit_real
    assert carry.size == 0
    words = (np.concatenate(word_parts) if word_parts
             else np.zeros(0, np.uint32))
    return PackedText(words=jnp.asarray(words),
                      n_real=jnp.asarray(n_real, jnp.int32),
                      bits=bits, terminal=alphabet.terminal_code)


def gather_symbols_dense(pt: PackedText, offs: jax.Array, w: int) -> jax.Array:
    """Read ``w`` symbol codes at each offset from dense storage.

    Returns (F, w) int32 codes with the virtual terminal substituted for
    positions ``>= n_real`` — element-for-element what a byte-path
    ``jnp.take`` from the terminal-padded string returns.  Pure-jnp; the
    Pallas realization is :mod:`repro.kernels.packed_gather`.
    """
    bits, spw = pt.bits, pt.syms_per_word
    offs = offs.astype(jnp.int32)
    aligned = _aligned_words(pt, offs, w)                       # (F, nw)
    shifts = (32 - bits * (jnp.arange(spw, dtype=jnp.uint32) + 1))
    sym = ((aligned[:, :, None] >> shifts[None, None, :]) & ((1 << bits) - 1))
    sym = sym.reshape(offs.shape[0], -1)[:, :w].astype(jnp.int32)
    past_end = (offs[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
                >= pt.n_real)
    return jnp.where(past_end, jnp.int32(pt.terminal), sym)


def _aligned_words(pt: PackedText, offs: jax.Array, w: int) -> jax.Array:
    """(F, ceil(w/spw)) uint32 dense words, shift-aligned to each offset."""
    bits, spw = pt.bits, pt.syms_per_word
    nw = -(-w // spw)
    word0 = offs // spw
    idx = word0[:, None] + jnp.arange(nw + 1, dtype=jnp.int32)[None, :]
    idx = jnp.minimum(idx, pt.words.shape[0] - 1)  # safety net (cf. gather_pack)
    words = jnp.take(pt.words, idx, axis=0).astype(jnp.uint32)  # (F, nw+1)
    sh = (bits * (offs % spw)).astype(jnp.uint32)[:, None]
    hi = words[:, :-1] << sh
    # funnel low half as (x >> 1) >> (31 - sh): equals x >> (32 - sh) for
    # sh > 0 and 0 for sh == 0, with every shift amount in-range — no
    # select needed (selects + masked shifts dominate this path on CPU).
    lo = (words[:, 1:] >> 1) >> (31 - sh)
    return hi | lo


def _spread_to_bytes(chunk: jax.Array, bits: int) -> jax.Array:
    """Spread 4 right-aligned ``bits``-bit fields of a 32-bit lane (uint32,
    or int32 inside Pallas kernels) into the 4 big-endian bytes of the
    lane (classic bit-interleave deposit)."""
    if bits == 8:
        return chunk
    if bits == 4:
        t = (chunk | (chunk << 8)) & 0x00FF00FF
        return (t | (t << 4)) & 0x0F0F0F0F
    if bits == 2:
        t = (chunk | (chunk << 12)) & 0x000F000F
        return (t | (t << 6)) & 0x03030303
    raise ValueError(f"unsupported dense bits {bits}")


def gather_pack_dense(pt: PackedText, offs: jax.Array, w: int) -> jax.Array:
    """Gather ``w`` symbols from dense storage and emit byte sort keys.

    Bit-identical to :func:`gather_pack` on the terminal-padded byte
    string — the invariant the whole dense pipeline rests on (asserted in
    ``tests/test_packed.py``) — while moving ``bits/8`` of the bytes.

    The repack never materializes individual symbols: each output int32
    carries 4 symbols = ``4*bits`` consecutive dense bits, so it is one
    chunk-extract + bit-spread per OUTPUT word (4x fewer lanes than the
    per-symbol route), and the virtual-terminal tail is patched per word
    through a 5-entry keep/terminal mask table.
    """
    bits, spw = pt.bits, pt.syms_per_word
    assert w % 4 == 0, w
    offs = offs.astype(jnp.int32)
    f = offs.shape[0]
    n_out = w // 4
    aligned = _aligned_words(pt, offs, w)  # (F, ceil(w/spw))
    cpw = spw // 4  # output chunks per dense word
    if cpw > 1:
        csh = (32 - (4 * bits) * (jnp.arange(cpw, dtype=jnp.uint32) + 1))
        chunks = ((aligned[:, :, None] >> csh[None, None, :])
                  & jnp.uint32((1 << (4 * bits)) - 1))
        chunks = chunks.reshape(f, aligned.shape[1] * cpw)[:, :n_out]
    else:
        chunks = aligned[:, :n_out]
    out = _spread_to_bytes(chunks, bits)  # (F, n_out) big-endian byte words

    # virtual terminal: word j holds symbols off+4j .. off+4j+3; keep the
    # first v = clip(n_real - (off+4j), 0, 4) and overwrite the tail with
    # terminal bytes (= t_word on the dropped bytes: term == t_word & ~keep)
    t_word = jnp.uint32((pt.terminal & 0xFF) * 0x01010101)
    keep_tab = jnp.asarray(
        np.array([0, 0xFF000000, 0xFFFF0000, 0xFFFFFF00, 0xFFFFFFFF],
                 np.uint32))
    v = jnp.clip(pt.n_real - (offs[:, None]
                              + 4 * jnp.arange(n_out, dtype=jnp.int32)[None, :]),
                 0, 4)
    keep = keep_tab[v]
    out = (out & keep) | (t_word & ~keep)
    return jax.lax.bitcast_convert_type(out, jnp.int32)


# ---------------------------------------------------------------------------
# Word-parallel comparison primitives (dense words AS the compare currency)
# ---------------------------------------------------------------------------


def syms_per_word(bits: int) -> int:
    return 32 // bits


def sub_code(bits: int, terminal: int) -> int:
    """The code substituted for the virtual terminal in dense word reads.

    The largest representable code: when the terminal itself fits ``bits``
    (4-bit protein classes, 8-bit byte) this IS the terminal and word
    reads are faithful; a saturated alphabet (2-bit DNA, terminal code 4)
    substitutes the largest real code and relies on the per-row limit to
    keep comparisons exact (see the module docstring)."""
    return min(terminal, (1 << bits) - 1)


def _sub_word(bits: int, terminal: int) -> int:
    """``sub_code`` replicated across every field of a uint32 word."""
    sub = sub_code(bits, terminal)
    return sum(sub << (bits * k) for k in range(syms_per_word(bits)))


def pack_dense(sym: jax.Array, bits: int) -> jax.Array:
    """(…, m) symbol codes (< 2**bits) → (…, ceil(m/spw)) uint32 dense
    big-endian words, zero-padded past ``m`` — the pattern-side packing
    that mirrors what :func:`pack_text` stores for the string."""
    *lead, m = sym.shape
    spw = syms_per_word(bits)
    m_pad = -(-m // spw) * spw
    sym = sym.astype(jnp.uint32)
    if m_pad != m:
        pad = jnp.zeros((*lead, m_pad - m), jnp.uint32)
        sym = jnp.concatenate([sym, pad], axis=-1)
    grp = sym.reshape(*lead, m_pad // spw, spw)
    shifts = (32 - bits * (jnp.arange(spw, dtype=jnp.uint32) + 1))
    return jnp.sum(grp << shifts, axis=-1).astype(jnp.uint32)


def pack_pattern_dense(sym: jax.Array, bits: int, terminal: int) -> jax.Array:
    """Pack a (…, m) pattern/window batch to dense words, substituting the
    terminal code (``jnp.minimum`` with :func:`sub_code` — the identity
    for every code a valid pattern may hold except a too-wide terminal)."""
    sub = jnp.uint32(sub_code(bits, terminal))
    return pack_dense(jnp.minimum(sym.astype(jnp.uint32), sub), bits)


def gather_words_dense(pt: PackedText, offs: jax.Array, w: int) -> jax.Array:
    """(F, ceil(w/spw)) uint32 dense words, shift-aligned to each offset,
    with :func:`sub_code` substituted for every position ``>= n_real``.

    This is the word-compare analogue of :func:`gather_pack_dense`: the
    raw comparison keys, never spread back to bytes.  Pure-jnp; the
    Pallas realization is ``repro.kernels.packed_gather.range_gather_words``.
    """
    bits, spw = pt.bits, pt.syms_per_word
    offs = offs.astype(jnp.int32)
    aligned = _aligned_words(pt, offs, w)                        # (F, nw)
    nw = aligned.shape[1]
    # keep the first v = clip(n_real - word_start, 0, spw) fields of each
    # word; overwrite the tail with the substituted terminal pattern
    starts = offs[:, None] + spw * jnp.arange(nw, dtype=jnp.int32)[None, :]
    v = jnp.clip(pt.n_real - starts, 0, spw)
    full = jnp.uint32(0xFFFFFFFF)
    # shift stays in-range: v >= 1 rows shift by <= 32 - bits; v == 0 is
    # overridden by the where
    keep = jnp.where(
        v > 0,
        full << ((spw - jnp.maximum(v, 1)) * bits).astype(jnp.uint32),
        jnp.uint32(0))
    sub_w = jnp.uint32(_sub_word(bits, pt.terminal))
    return (aligned & keep) | (sub_w & ~keep)


def word_limit(n_real, offs: jax.Array, w: int) -> jax.Array:
    """Symbol index of the first (virtual) terminal in a width-``w`` read
    at each offset, clipped to [0, w] — the per-row comparison limit."""
    return jnp.clip(n_real - offs.astype(jnp.int32), 0, w)


def lcp_words(a: jax.Array, b: jax.Array, bits: int) -> jax.Array:
    """First differing SYMBOL index of (F, NW) uint32 dense word rows:
    XOR, first non-zero word, count-leading-zeros → field index.  Rows
    equal through all NW words return ``NW * spw``."""
    spw = syms_per_word(bits)
    nw = a.shape[-1]
    x = a ^ b
    neq = x != 0
    any_neq = jnp.any(neq, axis=-1)
    wi = jnp.argmax(neq, axis=-1).astype(jnp.int32)
    xw = jnp.take_along_axis(x, wi[..., None], axis=-1)[..., 0]
    sym = clz32(xw) // bits
    return jnp.where(any_neq, wi * spw + sym, nw * spw)


def extract_sym(words: jax.Array, idx: jax.Array, bits: int) -> jax.Array:
    """The ``bits``-wide field at symbol index ``idx`` of each word row."""
    spw = syms_per_word(bits)
    wv = jnp.take_along_axis(words, (idx // spw)[..., None], axis=-1)[..., 0]
    sh = (32 - bits * (idx % spw + 1)).astype(jnp.uint32)
    return ((wv >> sh) & ((1 << bits) - 1)).astype(jnp.int32)


def lcp_words_limited(a: jax.Array, b: jax.Array, lim_a: jax.Array,
                      lim_b: jax.Array, w: int, bits: int) -> jax.Array:
    """Row LCP in symbols, capped at ``w``, of substituted dense word rows
    with per-row terminal limits: ``min(first_diff, lim_a, lim_b, w)``.

    Exact vs the byte scan whenever ``lim_a != lim_b`` or the rows carry
    matching all-terminal tails past a common limit (suffix-vs-suffix
    always; window-vs-suffix for embedded-terminal-free queries)."""
    p = lcp_words(a, b, bits)
    return jnp.minimum(jnp.minimum(jnp.minimum(p, lim_a), lim_b),
                       w).astype(jnp.int32)


def lcp_adjacent_words(prev: jax.Array, cur: jax.Array, lim_prev: jax.Array,
                       lim_cur: jax.Array, w: int, bits: int, terminal: int):
    """Word-key analogue of ``prepare.lcp_adjacent``: (lcp, c1, c2) per
    row, with the true terminal code restored at a divergence that falls
    ON a row's limit (the substituted field there is :func:`sub_code`,
    but the suffix really holds ``$``).  Fully-equal rows (lcp == w)
    report c1 == c2 == 0, matching the byte oracle."""
    spw = syms_per_word(bits)
    nw = cur.shape[-1]
    lcp = lcp_words_limited(prev, cur, lim_prev, lim_cur, w, bits)
    idx = jnp.clip(lcp, 0, nw * spw - 1)
    ca = extract_sym(prev, idx, bits)
    cb = extract_sym(cur, idx, bits)
    diverged = lcp < w
    c1 = jnp.where(diverged, jnp.where(lim_prev == lcp, terminal, ca), 0)
    c2 = jnp.where(diverged, jnp.where(lim_cur == lcp, terminal, cb), 0)
    return lcp, c1.astype(jnp.int32), c2.astype(jnp.int32)


def word_sort_keys(pt: PackedText, offs: jax.Array, w: int,
                   gather_words=None) -> tuple[jax.Array, jax.Array]:
    """(keys, tie) for the elastic-range sort on dense word keys.

    keys: (F, ceil(w/spw)) uint32 substituted dense words; tie: (F,)
    int32 ``w - limit``, the LEAST significant sort key.  Substituted
    keys that compare equal through ``w`` symbols differ from the byte
    keys only where a terminal was substituted — and there the row whose
    terminal comes FIRST is lexicographically larger, which is exactly
    ascending ``w - limit``.  Rows with no terminal in the window tie at
    0, preserving the stable order the byte path keeps."""
    gather = gather_words or gather_words_dense
    keys = gather(pt, offs, w)
    tie = (w - word_limit(pt.n_real, offs, w)).astype(jnp.int32)
    return keys, tie


def unpack_text(pt: PackedText, n: int | None = None) -> np.ndarray:
    """Decode dense storage back to uint8 codes (terminal included).

    ``n``: total symbols to materialize (default ``n_real + 1``, i.e. the
    original terminated string)."""
    n_real = int(pt.n_real)
    n = n_real + 1 if n is None else int(n)
    spw = pt.syms_per_word
    words = np.asarray(pt.words)
    shifts = (32 - pt.bits * (np.arange(spw, dtype=np.uint32) + 1))
    sym = ((words[:, None] >> shifts[None, :]) & ((1 << pt.bits) - 1))
    sym = sym.reshape(-1)[:n].astype(np.uint8)
    sym[n_real:] = pt.terminal
    return sym

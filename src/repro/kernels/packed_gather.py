"""Pallas TPU kernels over the DENSE k-bit packed string (paper §6.1).

Five kernels share one in-kernel dense read (:mod:`repro.kernels.tiles`:
each read's window is DMA'd from HBM, lane-aligned and transposed so that
word ``j`` of read ``r`` sits at ``[j, r]``).  The byte-key family
repacks dense reads into byte-per-symbol sort keys:

* :func:`range_gather_packed` — the packed realization of
  :mod:`repro.kernels.range_gather`: gather ``w`` symbols per offset from
  the ``bits``-bit packed word stream and emit the SAME big-endian
  byte-per-symbol int32 sort keys the unpacked path produces, so every
  downstream lexsort / LCP runs unchanged while the HBM string read
  shrinks by ``8/bits`` (4x for DNA).
* :func:`pattern_probe_packed` — the packed probe-gather-compare step of
  the batched query binary search (:mod:`repro.kernels.pattern_probe`).

The word-compare family keeps the dense words AS the comparison currency
(no byte repack in-kernel, ``bits/8`` of the compare lanes — the ERA §6.1
packing argument taken to its end; terminal semantics live in
:mod:`repro.core.packing`'s word-comparison rules):

* :func:`range_gather_words` — raw shift-aligned uint32 word rows with
  the virtual terminal substituted (:func:`repro.core.packing.sub_code`);
* :func:`pattern_probe_words` — compares k-bit pattern words against the
  shifted text words directly, verdict via XOR + first-word + clz +
  terminal-limit rules;
* :func:`suffix_lcp_words` — suffix-pair LCP as first-differing-word +
  count-leading-zeros, capped by both terminal limits.

Dense-read recipe: the ``nw + 1`` words covering a read are funnel-shifted
across the sub-word bit offset (``off % syms_per_word``)
(:func:`repro.kernels.tiles.aligned_words`); the word family substitutes
the virtual terminal for positions ``>= n_real`` (dense storage holds
only REAL symbols — see :class:`repro.core.packing.PackedText`), the
byte-key family spreads each 4-symbol field to one byte per symbol and
patches the terminal bytes.

The pure-jnp oracles are :func:`repro.core.packing.gather_pack_dense` /
``repro.kernels.ref.pattern_probe_packed_ref``; ``tests/test_packed.py``
asserts exact equality in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.packing import PackedText, _spread_to_bytes, _sub_word, clz32
from repro.kernels.tiles import (
    aligned_words,
    as_i32,
    paged_call,
    per_read,
    srl,
    stage_rows,
    n_windows,
)

SIGN = as_i32(1 << 31)


def stage_packed(pt: PackedText, nw: int):
    """The dense words of ``pt`` staged for a read of ``nw`` words."""
    return stage_rows(lax.bitcast_convert_type(pt.words, jnp.int32),
                      n_windows(nw))


def byte_key_row(ut_ref, off, n_real, k: int, *, bits: int,
                 terminal: int) -> jax.Array:
    """Byte sort-key word ``k`` (symbols ``off + 4k .. off + 4k + 3``) of
    every read as a ``(1, R)`` row, the virtual terminal patched in."""
    cpw = 32 // bits // 4
    j, q = divmod(k, cpw)
    word = aligned_words(ut_ref, off, j, j + 1, bits)
    if bits < 8:
        word = srl(word, 32 - 4 * bits * (q + 1)) & ((1 << (4 * bits)) - 1)
    key = _spread_to_bytes(word, bits)
    v = jnp.clip(n_real - (off + 4 * k), 0, 4)
    keep = jnp.where(v > 0, jnp.int32(-1) << (8 * (4 - jnp.maximum(v, 1))), 0)
    return (key & keep) | (as_i32((terminal & 0xFF) * 0x01010101) & ~keep)


def probe_rows(key_rows, pat_ref, mask_ref) -> jax.Array:
    """Sign of masked byte-key rows vs pattern rows: the first differing
    word decides, compared unsigned (sign-flipped).  ``(1, R)`` in
    {-1, 0, +1}."""
    cmp = None
    for k, key in enumerate(key_rows):
        sw = key & mask_ref[k:k + 1, :]
        pat = pat_ref[k:k + 1, :]
        lt = (sw ^ SIGN) < (pat ^ SIGN)
        verdict = jnp.where(sw != pat, jnp.where(lt, -1, 1), 0)
        cmp = verdict if cmp is None else jnp.where(cmp == 0, verdict, cmp)
    return cmp


def substitute(words: jax.Array, off: jax.Array, n_real, *, bits: int,
               terminal: int) -> jax.Array:
    """Keep the first ``v = clip(n_real - start, 0, spw)`` fields of each
    word (``start`` its first symbol) and substitute
    :func:`repro.core.packing.sub_code` for the rest."""
    spw = 32 // bits
    starts = off + spw * lax.broadcasted_iota(jnp.int32, words.shape, 0)
    v = jnp.clip(n_real - starts, 0, spw)
    keep = jnp.where(v > 0,
                     jnp.int32(-1) << ((spw - jnp.maximum(v, 1)) * bits), 0)
    return (words & keep) | (as_i32(_sub_word(bits, terminal)) & ~keep)


def first_diff(a: jax.Array, b: jax.Array, bits: int):
    """Column-wise first difference of ``(nw, R)`` word blocks:
    ``(p, aw, bw, sym)`` — the first differing symbol index
    (``nw * spw`` when equal), the words holding it and its field index
    inside them."""
    nw = a.shape[0]
    spw = 32 // bits
    x = a ^ b
    rows = lax.broadcasted_iota(jnp.int32, a.shape, 0)
    first = jnp.min(jnp.where(x != 0, rows, nw), axis=0, keepdims=True)
    sel = rows == first
    pick = lambda v: jnp.sum(jnp.where(sel, v, 0), axis=0, keepdims=True)
    sym = clz32(pick(x)) // bits
    p = jnp.where(first < nw, first * spw + sym, nw * spw)
    return p, pick(a), pick(b), jnp.minimum(sym, spw - 1)


def word_verdict(sw, pat, pos, cmp_len, lim_p, n_real, *, bits: int):
    """Word-compare probe verdict (``kernels.ref.probe_words_ref`` rules):
    a difference below both terminal limits decides by symbol; otherwise
    the side whose limit comes first is larger."""
    nw = sw.shape[0]
    big = nw * (32 // bits)
    p, aw, bw, sym = first_diff(sw, pat, bits)
    sh = 32 - bits * (sym + 1)
    ones = (1 << bits) - 1
    ca = srl(aw, sh) & ones
    cb = srl(bw, sh) & ones
    sym_sign = jnp.where(ca < cb, -1, 1)
    ls = n_real - pos
    ls = jnp.where(ls < cmp_len, ls, big)
    lp = jnp.where(lim_p < cmp_len, lim_p, big)
    lim_sign = jnp.where(ls < lp, 1, jnp.where(lp < ls, -1, 0))
    return jnp.where(p < jnp.minimum(ls, lp), sym_sign, lim_sign)


# ---------------------------------------------------------------------------
# Byte-key family
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("w", "tile", "interpret"))
def range_gather_packed(
    pt: PackedText,
    offs: jax.Array,
    w: int,
    *,
    tile: int = 2048,
    interpret: bool | None = None,
) -> jax.Array:
    """Gather ``w`` symbols per offset from dense storage; emit byte keys.

    pt: the dense-packed string (its word tail must cover every read —
    the ``extra`` contract of :func:`repro.core.packing.pack_text`);
    offs: (F,) int32.  Returns (F, w//4) int32, bit-identical to
    :func:`repro.kernels.range_gather.range_gather_pack` on the
    terminal-padded byte string.  ``tile``: reads per grid step.
    """
    assert w % 4 == 0, w
    nw = -(-w // pt.syms_per_word)

    def body(sc, offs_, uts, _, outs):
        for k in range(w // 4):
            outs[0][k:k + 1, :] = byte_key_row(
                uts[0], offs_[0], sc[0], k, bits=pt.bits, terminal=pt.terminal)

    def call(pt, offs):
        rows, n_rows = stage_packed(pt, nw)
        (keys,) = paged_call(body, rows, n_rows, spw=pt.syms_per_word, nw=nw,
                             starts=[offs], scalars=[pt.n_real],
                             out_rows=[w // 4], tile=tile, interpret=interpret)
        return keys.T

    return per_read(call, pt, offs)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def pattern_probe_packed(
    pt: PackedText,
    pos: jax.Array,
    pat_words: jax.Array,
    mask_words: jax.Array,
    *,
    tile: int = 2048,
    interpret: bool | None = None,
) -> jax.Array:
    """Packed-storage probe: compare each suffix against its pattern row.

    pos: (B,) int32 suffix positions; pat_words/mask_words: (B, W) int32
    byte-packed + masked pattern rows (the same host-side packing the byte
    probe uses).  Returns int32[B] in {-1, 0, +1}; bit-identical to
    :func:`repro.kernels.pattern_probe.pattern_probe` on the byte string.
    """
    b, n_words = pat_words.shape
    assert mask_words.shape == (b, n_words) and pos.shape == (b,)
    nw = -(-(n_words * 4) // pt.syms_per_word)

    def body(sc, offs_, uts, vecs, outs):
        keys = [byte_key_row(uts[0], offs_[0], sc[0], k, bits=pt.bits,
                             terminal=pt.terminal) for k in range(n_words)]
        outs[0][...] = probe_rows(keys, vecs[0], vecs[1])

    def call(pt, pos, pat, mask):
        rows, n_rows = stage_packed(pt, nw)
        (cmp,) = paged_call(body, rows, n_rows, spw=pt.syms_per_word, nw=nw,
                            starts=[pos], vecs=[pat.T, mask.T],
                            scalars=[pt.n_real], out_rows=[1], tile=tile,
                            interpret=interpret)
        return cmp[0]

    return per_read(call, pt, pos, pat_words, mask_words)


# ---------------------------------------------------------------------------
# Word-compare family: dense uint32 words are the comparison currency
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("w", "tile", "interpret"))
def range_gather_words(
    pt: PackedText,
    offs: jax.Array,
    w: int,
    *,
    tile: int = 2048,
    interpret: bool | None = None,
) -> jax.Array:
    """Gather the ``ceil(w / spw)`` dense uint32 words covering ``w``
    symbols at each offset — shift-aligned, terminal-substituted, never
    spread to bytes.  Returns (F, nw) uint32, bit-identical to
    :func:`repro.core.packing.gather_words_dense`.
    """
    nw = -(-w // pt.syms_per_word)

    def body(sc, offs_, uts, _, outs):
        words = aligned_words(uts[0], offs_[0], 0, nw, pt.bits)
        outs[0][...] = substitute(words, offs_[0], sc[0], bits=pt.bits,
                                  terminal=pt.terminal)

    def call(pt, offs):
        rows, n_rows = stage_packed(pt, nw)
        (words,) = paged_call(body, rows, n_rows, spw=pt.syms_per_word,
                              nw=nw, starts=[offs], scalars=[pt.n_real],
                              out_rows=[nw], tile=tile, interpret=interpret)
        return lax.bitcast_convert_type(words.T, jnp.uint32)

    return per_read(call, pt, offs)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def pattern_probe_words(
    pt: PackedText,
    pos: jax.Array,
    pat_dense: jax.Array,
    mask_dense: jax.Array,
    lengths: jax.Array,
    lim_p: jax.Array | None = None,
    *,
    tile: int = 2048,
    interpret: bool | None = None,
) -> jax.Array:
    """Word-compare probe: k-bit pattern words vs shifted text words.

    pat_dense / mask_dense: (B, NW) uint32 dense rows from
    :func:`repro.core.packing.pack_pattern_dense` (zero / all-ones fields
    past each compare length); lengths: (B,) int32 compare lengths;
    lim_p: the pattern side's first-terminal index for terminal-padded
    windows (defaults to ``lengths`` — no pattern terminal).  Returns
    int32[B] in {-1, 0, +1}; bit-identical to the byte probe for
    real-symbol patterns (oracle:
    :func:`repro.kernels.ref.pattern_probe_words_ref`).
    """
    b, nw = pat_dense.shape
    assert mask_dense.shape == (b, nw) and pos.shape == (b,)
    if lim_p is None:
        lim_p = lengths

    def body(sc, offs_, uts, vecs, outs):
        pat, mask, cmp_len, lp = (r[...] for r in vecs)
        sw = substitute(aligned_words(uts[0], offs_[0], 0, nw, pt.bits),
                        offs_[0], sc[0], bits=pt.bits, terminal=pt.terminal)
        outs[0][...] = word_verdict(sw & mask, pat, offs_[0], cmp_len, lp,
                                    sc[0], bits=pt.bits)

    def call(pt, pos, pat, mask, lengths, lim_p):
        rows, n_rows = stage_packed(pt, nw)
        i32 = lambda x: lax.bitcast_convert_type(x, jnp.int32).T
        (cmp,) = paged_call(body, rows, n_rows, spw=pt.syms_per_word, nw=nw,
                            starts=[pos],
                            vecs=[i32(pat), i32(mask), lengths[None, :],
                                  lim_p[None, :]],
                            scalars=[pt.n_real], out_rows=[1], tile=tile,
                            interpret=interpret)
        return cmp[0]

    return per_read(call, pt, pos, pat_dense, mask_dense,
                    lengths.astype(jnp.int32), lim_p.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("w", "tile", "interpret"))
def suffix_lcp_words(
    pt: PackedText,
    pos_a: jax.Array,
    pos_b: jax.Array,
    w: int,
    *,
    tile: int = 2048,
    interpret: bool | None = None,
) -> jax.Array:
    """Word-compare suffix-pair LCP over dense storage, capped at ``w``.

    Finds the first differing dense word by XOR, resolves the symbol
    offset with count-leading-zeros, and caps at both terminal limits —
    equal to the byte symbol scan for distinct suffix pairs (oracle:
    :func:`repro.kernels.ref.suffix_lcp_words_ref`).
    """
    nw = -(-w // pt.syms_per_word)
    assert pos_b.shape == pos_a.shape

    def body(sc, offs_, uts, _, outs):
        oa, ob = offs_
        nr = sc[0]
        a, b = (substitute(aligned_words(ut, o, 0, nw, pt.bits), o, nr,
                           bits=pt.bits, terminal=pt.terminal)
                for ut, o in zip(uts, offs_))
        p = first_diff(a, b, pt.bits)[0]
        la = jnp.clip(nr - oa, 0, w)
        lb = jnp.clip(nr - ob, 0, w)
        outs[0][...] = jnp.minimum(jnp.minimum(jnp.minimum(p, la), lb), w)

    def call(pt, pos_a, pos_b):
        rows, n_rows = stage_packed(pt, nw)
        (lcp,) = paged_call(body, rows, n_rows, spw=pt.syms_per_word, nw=nw,
                            starts=[pos_a, pos_b], scalars=[pt.n_real],
                            out_rows=[1], tile=tile, interpret=interpret)
        return lcp[0]

    return per_read(call, pt, pos_a, pos_b)

"""Fused probe+gather Pallas kernels: find-and-fetch in ONE launch.

A serving-shaped "find and fetch" query both *locates* a pattern's
suffix-array range and *returns* the matched text window.  Composed from
the existing kernel family that is two launches over the same HBM window:
a probe (:func:`repro.kernels.packed_gather.pattern_probe_words` /
``pattern_probe_packed``) followed by a gather
(:func:`repro.kernels.packed_gather.range_gather_words` /
``range_gather_packed``) at the same position — the string window is
DMA'd twice.  These kernels fuse the two: one dense read per row feeds
BOTH the comparison verdict and the gathered window, halving launches and
string traffic on the serving hot path (:mod:`repro.launch.serving`).

Two currencies, mirroring the probe family:

* :func:`probe_gather_words`  — word-compare verdict + raw shift-aligned
  substituted dense uint32 word rows (the PR-5 comparison currency);
* :func:`probe_gather_packed` — byte-key verdict + big-endian
  byte-per-symbol int32 sort-key rows (the PR-4 oracle currency).

Both are bit-identical to the two-launch composition of their family's
probe and gather kernels (the refs in :mod:`repro.kernels.ref` ARE that
composition; ``tests/test_packed.py`` pins kernel == ref == composition
under every oracle leg).  The fetch width is independent of the pattern
width: the kernel reads ``max(pattern, fetch)`` symbols once and slices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.packing import PackedText
from repro.kernels.packed_gather import (
    byte_key_row,
    probe_rows,
    stage_packed,
    substitute,
    word_verdict,
)
from repro.kernels.tiles import aligned_words, paged_call, per_read


@functools.partial(jax.jit, static_argnames=("fetch", "tile", "interpret"))
def probe_gather_words(
    pt: PackedText,
    pos: jax.Array,
    pat_dense: jax.Array,
    mask_dense: jax.Array,
    lengths: jax.Array,
    lim_p: jax.Array | None = None,
    *,
    fetch: int,
    tile: int = 2048,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused word-compare probe + word gather: one read, two results.

    Arguments match :func:`repro.kernels.packed_gather.pattern_probe_words`
    plus the static ``fetch`` width in symbols.  Returns
    ``(cmp int32[B], win uint32[B, ceil(fetch/spw)])`` — ``cmp`` equal to
    the probe kernel, ``win`` equal to ``range_gather_words(pt, pos,
    fetch)`` (oracle: :func:`repro.kernels.ref.probe_gather_words_ref`).
    """
    b, nw_pat = pat_dense.shape
    nw_out = -(-fetch // pt.syms_per_word)
    nw_rd = max(nw_pat, nw_out)
    assert mask_dense.shape == (b, nw_pat) and pos.shape == (b,)
    if lim_p is None:
        lim_p = lengths

    def body(sc, offs_, uts, vecs, outs):
        pat, mask, cmp_len, lp = (r[...] for r in vecs)
        sw = substitute(aligned_words(uts[0], offs_[0], 0, nw_rd, pt.bits),
                        offs_[0], sc[0], bits=pt.bits, terminal=pt.terminal)
        # gather half: the first nw_out substituted words ARE what
        # range_gather_words emits (per-word substitution is independent)
        outs[1][...] = sw[:nw_out]
        # probe half: identical to packed_gather.pattern_probe_words
        outs[0][...] = word_verdict(sw[:nw_pat] & mask, pat, offs_[0],
                                    cmp_len, lp, sc[0], bits=pt.bits)

    def call(pt, pos, pat, mask, lengths, lim_p):
        rows, n_rows = stage_packed(pt, nw_rd)
        i32 = lambda x: lax.bitcast_convert_type(x, jnp.int32).T
        cmp, win = paged_call(body, rows, n_rows, spw=pt.syms_per_word,
                              nw=nw_rd, starts=[pos],
                              vecs=[i32(pat), i32(mask), lengths[None, :],
                                    lim_p[None, :]],
                              scalars=[pt.n_real], out_rows=[1, nw_out],
                              tile=tile, interpret=interpret)
        return cmp[0], lax.bitcast_convert_type(win.T, jnp.uint32)

    return per_read(call, pt, pos, pat_dense, mask_dense,
                    lengths.astype(jnp.int32), lim_p.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("fetch", "tile", "interpret"))
def probe_gather_packed(
    pt: PackedText,
    pos: jax.Array,
    pat_words: jax.Array,
    mask_words: jax.Array,
    *,
    fetch: int,
    tile: int = 2048,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused byte-key probe + byte-key gather over dense storage.

    Arguments match :func:`repro.kernels.packed_gather.pattern_probe_packed`
    plus the static ``fetch`` width (symbols, multiple of 4).  Returns
    ``(cmp int32[B], keys int32[B, fetch//4])`` — ``cmp`` equal to the
    packed probe, ``keys`` equal to ``range_gather_packed(pt, pos, fetch)``
    (oracle: :func:`repro.kernels.ref.probe_gather_packed_ref`).
    """
    assert fetch % 4 == 0, fetch
    b, n_words = pat_words.shape
    assert mask_words.shape == (b, n_words) and pos.shape == (b,)
    n_keys = max(n_words, fetch // 4)
    nw_rd = -(-(n_keys * 4) // pt.syms_per_word)

    def body(sc, offs_, uts, vecs, outs):
        keys = [byte_key_row(uts[0], offs_[0], sc[0], k, bits=pt.bits,
                             terminal=pt.terminal) for k in range(n_keys)]
        for k in range(fetch // 4):  # gather half == range_gather_packed
            outs[1][k:k + 1, :] = keys[k]
        outs[0][...] = probe_rows(keys[:n_words], vecs[0], vecs[1])

    def call(pt, pos, pat, mask):
        rows, n_rows = stage_packed(pt, nw_rd)
        cmp, win = paged_call(body, rows, n_rows, spw=pt.syms_per_word,
                              nw=nw_rd, starts=[pos], vecs=[pat.T, mask.T],
                              scalars=[pt.n_real], out_rows=[1, fetch // 4],
                              tile=tile, interpret=interpret)
        return cmp[0], win.T

    return per_read(call, pt, pos, pat_words, mask_words)

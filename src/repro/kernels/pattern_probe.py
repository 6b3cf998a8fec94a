"""Pallas TPU kernel: batched probe-gather-compare (ERA substring queries).

The device-resident query engine (:mod:`repro.core.query`) resolves a batch
of patterns by vectorized lower/upper-bound binary search over the leaf
array ``L`` (= the suffix array restricted to each sub-tree's prefix).  The
inner step of that search is this kernel: for each probe position, gather
``w`` symbols of the suffix from S, pack them big-endian into int32 words,
mask past the pattern length, and emit the sign of the comparison with the
pre-packed pattern row.

Layout mirrors :mod:`repro.kernels.range_gather`: each probe's window is
DMA'd from the staged 8-bit words in HBM, the pattern/mask rows arrive as
lane-dense column blocks, and each read writes one comparison verdict.
Comparisons run on the sign-flipped words so signed int32 order equals
unsigned (lexicographic) order — required for the byte alphabet whose
codes reach the top bit.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.packed_gather import probe_rows
from repro.kernels.range_gather import stage_bytes
from repro.kernels.tiles import aligned_words, paged_call, per_read


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def pattern_probe(
    s_padded: jax.Array,
    pos: jax.Array,
    pat_words: jax.Array,
    mask_words: jax.Array,
    *,
    tile: int = 2048,
    interpret: bool | None = None,
) -> jax.Array:
    """Compare the suffix at each probe position against its pattern row.

    s_padded: (n,) integer codes (terminal-padded past every read);
    pos: (B,) int32; pat_words/mask_words: (B, W) int32 packed+masked.
    Returns int32[B] in {-1, 0, +1} (0 == suffix starts with pattern).
    ``interpret=None`` compiles on TPU and interprets elsewhere.
    """
    b, n_words = pat_words.shape
    assert mask_words.shape == (b, n_words) and pos.shape == (b,)

    def body(sc, offs_, uts, vecs, outs):
        keys = [aligned_words(uts[0], offs_[0], k, k + 1, 8)
                for k in range(n_words)]
        outs[0][...] = probe_rows(keys, vecs[0], vecs[1])

    def call(s, pos, pat, mask):
        rows, n_rows = stage_bytes(s, n_words)
        (cmp,) = paged_call(body, rows, n_rows, spw=4, nw=n_words,
                            starts=[pos], vecs=[pat.T, mask.T], out_rows=[1],
                            tile=tile, interpret=interpret)
        return cmp[0]

    return per_read(call, s_padded, pos, pat_words, mask_words)

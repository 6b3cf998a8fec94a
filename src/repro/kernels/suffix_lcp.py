"""Pallas TPU kernel: batched suffix-pair LCP (global LCP array assembly).

The analytics engine builds the GLOBAL LCP array over the flattened leaf
array (= the suffix array): intra-subtree entries are already known — they
are the ``b_off`` divergence depths SubTreePrepare emitted — so only the
T-1 cross-subtree boundary entries remain.  Those pairs come from DIFFERENT
prefix-free vertical-partition prefixes, so their LCP is strictly less than
the shorter prefix length: a single bounded-width comparison suffices, no
iterative deepening.

Layout mirrors :mod:`repro.kernels.pattern_probe`: both suffixes' windows
are DMA'd from the staged 8-bit words in HBM and each pair writes one LCP
value.  The first differing packed word and its count of leading zero
bits locate the first unequal symbol — identical to the packed-word
reference oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.packed_gather import first_diff
from repro.kernels.range_gather import stage_bytes
from repro.kernels.tiles import aligned_words, paged_call, per_read


@functools.partial(jax.jit, static_argnames=("w", "tile", "interpret"))
def suffix_lcp_pairs(
    s_padded: jax.Array,
    pos_a: jax.Array,
    pos_b: jax.Array,
    w: int,
    *,
    tile: int = 2048,
    interpret: bool | None = None,
) -> jax.Array:
    """LCP in symbols of the suffixes at ``pos_a[i]`` and ``pos_b[i]``.

    s_padded: (n,) integer codes (terminal-padded so ``pos + w`` reads stay
    in meaningful padding); pos_a, pos_b: (B,) int32.  Returns int32[B],
    capped at ``w`` (pairs equal through ``w`` symbols report exactly ``w``).
    ``interpret=None`` compiles on TPU and interprets elsewhere.
    """
    b = pos_a.shape[0]
    assert pos_b.shape == (b,)
    assert w % 4 == 0
    nw = w // 4

    def body(sc, offs_, uts, _, outs):
        a, b = (aligned_words(ut, o, 0, nw, 8) for ut, o in zip(uts, offs_))
        outs[0][...] = jnp.minimum(first_diff(a, b, 8)[0], w)

    def call(s, pos_a, pos_b):
        rows, n_rows = stage_bytes(s, nw)
        (lcp,) = paged_call(body, rows, n_rows, spw=4, nw=nw,
                            starts=[pos_a, pos_b], out_rows=[1], tile=tile,
                            interpret=interpret)
        return lcp[0]

    return per_read(call, s_padded, pos_a, pos_b)

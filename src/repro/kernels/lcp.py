"""Pallas TPU kernel: adjacent-row LCP + divergence symbols (ERA branching).

SubTreePrepare derives each ``B[i] = (c1, c2, offset)`` from the common
prefix of two adjacent sorted reads (paper lines 16-23).  The kernel
expands packed int32 words to bytes with shifts, finds the first unequal
byte with an iota-min reduction, and extracts the divergent symbols with a
one-hot sum — all VPU-shaped (no gathers, no scalar loops).

The caller supplies the shifted pair ``(a, b) = (rows[i-1], rows[i])``; the
shift-by-one is a cheap roll done in XLA where it fuses with the sort's
output layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiles


def _kernel(a_ref, b_ref, lcp_ref, c1_ref, c2_ref, *, w: int, n_words: int, blk: int):
    a = a_ref[...]
    b = b_ref[...]

    def to_bytes(x):  # unrolled byte expansion (no captured array consts)
        parts = [(x >> s) & 0xFF for s in (24, 16, 8, 0)]
        return jnp.stack(parts, axis=-1).reshape(blk, n_words * 4)

    ab = to_bytes(a)
    bb = to_bytes(b)
    neq = ab != bb
    iota = jax.lax.broadcasted_iota(jnp.int32, (blk, n_words * 4), 1)
    first = jnp.min(jnp.where(neq, iota, n_words * 4), axis=1)
    sel = iota == first[:, None]
    c1 = jnp.sum(jnp.where(sel, ab, 0), axis=1)
    c2 = jnp.sum(jnp.where(sel, bb, 0), axis=1)
    lcp_ref[...] = jnp.minimum(first, w)[:, None]
    c1_ref[...] = c1[:, None]
    c2_ref[...] = c2[:, None]


@functools.partial(jax.jit, static_argnames=("w", "blk", "interpret"))
def lcp_pairs(
    a: jax.Array,
    b: jax.Array,
    w: int,
    *,
    blk: int = 256,
    interpret: bool | None = None,
):
    """Row-wise LCP of packed key rows.  a, b: (F, W) int32; returns
    (lcp, c1, c2) int32[F] (fully-equal rows get lcp == w, c1 == c2 == 0).
    ``interpret=None`` compiles on TPU and interprets elsewhere."""
    f, n_words = a.shape
    assert b.shape == (f, n_words) and n_words * 4 >= w
    blk = min(blk, f)
    pad = (-f) % blk
    if pad:
        a = jnp.concatenate([a, jnp.zeros((pad, n_words), a.dtype)])
        b = jnp.concatenate([b, jnp.zeros((pad, n_words), b.dtype)])
    fp = f + pad

    outs = pl.pallas_call(
        functools.partial(_kernel, w=w, n_words=n_words, blk=blk),
        grid=(fp // blk,),
        in_specs=[
            pl.BlockSpec((blk, n_words), lambda i: (i, 0)),
            pl.BlockSpec((blk, n_words), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((fp, 1), jnp.int32),
            jax.ShapeDtypeStruct((fp, 1), jnp.int32),
            jax.ShapeDtypeStruct((fp, 1), jnp.int32),
        ],
        interpret=tiles.default_interpret(interpret),
    )(a, b)
    lcp, c1, c2 = (o[:f, 0] for o in outs)
    return lcp, c1, c2

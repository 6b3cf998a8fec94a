"""Pallas TPU kernel: elastic-range gather + pack (ERA's string read).

This is the TPU realization of the paper's "fill R by scanning S" step
(SubTreePrepare lines 9-12).  On disk the paper streams S sequentially; in
HBM the natural analogue is a *paged gather*: S stays in HBM, each read's
window of 128-lane rows is DMA'd into VMEM by hand (the paged-attention
pattern, :mod:`repro.kernels.tiles`), and ``w`` symbols come out packed
big-endian 4 per int32 so that integer comparisons equal lexicographic
symbol comparisons.

S is staged as those very words — 4 byte codes per int32
(:func:`repro.kernels.tiles.pack_bytes`) — so the packed sort key of a
read is the staged word stream funnel-shifted to the read's offset.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.tiles import (
    aligned_words,
    n_windows,
    pack_bytes,
    paged_call,
    per_read,
    stage_rows,
)


def stage_bytes(s_padded: jax.Array, nw: int):
    """The byte string staged as 8-bit words for a read of ``nw`` words."""
    return stage_rows(pack_bytes(s_padded), n_windows(nw))


@functools.partial(jax.jit, static_argnames=("w", "tile", "interpret"))
def range_gather_pack(
    s_padded: jax.Array,
    offs: jax.Array,
    w: int,
    *,
    tile: int = 2048,
    interpret: bool | None = None,
) -> jax.Array:
    """Gather ``w`` symbols per offset from S (terminal-padded) and pack.

    s_padded: (n,) integer codes;  offs: (F,) int32;  returns (F, w//4) int32.
    ``tile``: reads per grid step.  ``interpret=None`` compiles on TPU and
    interprets elsewhere.
    """
    assert w % 4 == 0, w
    nw = w // 4

    def body(sc, offs_, uts, _, outs):
        outs[0][...] = aligned_words(uts[0], offs_[0], 0, nw, 8)

    def call(s, offs):
        rows, n_rows = stage_bytes(s, nw)
        (keys,) = paged_call(body, rows, n_rows, spw=4, nw=nw, starts=[offs],
                             out_rows=[nw], tile=tile, interpret=interpret)
        return keys.T

    return per_read(call, s_padded, offs)

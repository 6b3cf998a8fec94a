"""Pallas TPU kernel: k-mer histogram (ERA vertical-partition counting).

The paper's VerticalPartitioning scans S once per working-set iteration and
counts the frequency of every candidate S-prefix.  On TPU this is a
streaming histogram: blocks of S (``(rows, 128)`` int32 codes) flow
HBM→VMEM with the next 8 rows as a halo for the ``k - 1`` lookahead,
rolling base-``|Σ|+1`` codes are built with ``k`` lane-shifted adds, and
counts accumulate into a VMEM ``(bins, 128)`` per-lane tally via a
compare-and-add against a column of bin ids (VPU-friendly; there is no
scatter on TPU).

The grid is ``(bin chunks, blocks of S)``: each chunk of up to 1024 bins
streams S once and, at its last block, folds the 128 lane tallies into
one lane-dense row of counts (transpose + sublane sum).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiles
from repro.kernels.tiles import LANES, round_up, stage_rows

BIN_CHUNK = 1024  # bins per pass over S (bounds the VMEM tally)


def _kernel(s_ref, halo_ref, out_ref, codes_ref, acc_ref, *, k: int,
            base: int, n: int, rows: int, chunk: int):
    c = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = s_ref[...]
    row = lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    lane = lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    # the row after each row: the next block's first row for the last one
    nxt = jnp.where(row == rows - 1, halo_ref[0:1, :],
                    pltpu.roll(x, rows - 1, 0))
    codes = x
    for d in range(1, k):  # k is small & static: unrolled shifted adds
        sym = jnp.where(lane < LANES - d, pltpu.roll(x, LANES - d, 1),
                        pltpu.roll(nxt, LANES - d, 1))
        codes = codes * base + sym
    # mask windows that start past the last suffix
    pos = (i * rows + row) * LANES + lane
    codes_ref[...] = jnp.where(pos < n, codes, -1)

    bins = c * chunk + lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)

    def tally(t, carry):
        hit = codes_ref[pl.ds(t, 1), :] == bins
        acc_ref[...] += hit.astype(jnp.int32)
        return carry
    lax.fori_loop(0, rows, tally, 0)

    @pl.when(i == pl.num_programs(1) - 1)
    def _fold():
        out_ref[0] = jnp.sum(acc_ref[...].T, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("n", "k", "base", "tile", "interpret"))
def kmer_histogram(
    s_padded: jax.Array,
    n: int,
    k: int,
    base: int,
    *,
    tile: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Counts of every base-``base`` k-mer over windows starting at 0..n-1.

    ``s_padded`` must be terminal-padded to >= n + k - 1 symbols.  Returns
    int32[base**k] (<= 2**16 bins).  ``tile``: symbols per grid step,
    rounded up to whole 8-row blocks.  ``interpret=None`` compiles on TPU
    and interprets elsewhere.
    """
    nbins = base**k
    assert nbins <= (1 << 16), "histogram too wide"
    assert k <= LANES
    rows = round_up(max(-(-tile // LANES), 1), 8)
    n_blocks = -(-n // (rows * LANES))
    # rows through the last block's halo must exist
    halo = n_blocks * rows + 8 - (-(-s_padded.shape[0] // LANES) + 1)
    s_rows, _ = stage_rows(s_padded, max(halo, 0))
    chunk = min(BIN_CHUNK, round_up(nbins, LANES))
    n_chunks = -(-nbins // chunk)

    out = pl.pallas_call(
        functools.partial(_kernel, k=k, base=base, n=n, rows=rows,
                          chunk=chunk),
        grid=(n_chunks, n_blocks),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda c, i: (i, 0)),
            # k-1 lookahead halo: the first 8 rows of the next block
            pl.BlockSpec((8, LANES), lambda c, i: ((i + 1) * (rows // 8), 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk), lambda c, i: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_chunks, 1, chunk), jnp.int32),
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.int32),
                        pltpu.VMEM((chunk, LANES), jnp.int32)],
        interpret=tiles.default_interpret(interpret),
    )(s_rows, s_rows)
    return out.reshape(-1)[:nbins]

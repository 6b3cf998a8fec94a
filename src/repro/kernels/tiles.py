"""Shared paged-read machinery for the gather-style Pallas kernels.

Every kernel that reads S at per-row offsets (``range_gather``,
``pattern_probe``, ``suffix_lcp``, the dense-word family in
``packed_gather`` / ``probe_gather``) stages the string the same way and
runs the same in-kernel read:

* **Staging** (:func:`stage_rows`): S as uint32 words, reshaped to
  ``(n_rows, 128)`` int32 rows padded with the last word plus halo rows,
  so the rows after any in-contract read always exist.  The array stays
  in HBM; 128-lane rows are the unit the TPU's DMA engine can fetch one at
  a time.
* **Blocking** (:func:`read_block`): reads are padded to whole blocks of
  ``R`` reads per grid step.  The per-read word offsets ride in SMEM
  (their 1-D blocks must be whole 1024-element tiles or the whole array),
  the per-read symbol offsets ride in VMEM as a lane-dense ``(1, R)`` row.
* **Reading** (:func:`read_windows`): in waves of 128 reads, DMA the
  ``nwin + 1`` HBM rows holding each read's window into VMEM (the next
  wave's DMAs are in flight while this wave is aligned), rotate each
  window so its first word sits in lane 0, and transpose the wave into a
  column-major ``(words, R)`` scratch: word ``j`` of read ``r`` at
  ``[j, r]``.  Everything after that is vectorized with reads on lanes,
  and every output block is ``(rows, R)`` — lane-dense, never padded.

Results are returned transposed back to the row-per-read layout the
callers and the ``kernels.ref`` oracles use.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128   # words per staged row (one DMA each)
WAVE = 128    # reads per DMA wave
SMEM_TILE = 1024  # 1-D int32 SMEM blocks: whole tiles or the whole array
MAX_BLOCK = 4096  # reads per grid step (bounds the VMEM scratch)


def default_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret=None`` default: compiled on TPU,
    interpreter elsewhere (a hard-coded True would leave real TPU runs
    interpreting forever).  The one policy site for every kernel."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


def pick_tile(kernel: str, *, n: int, dtype_bits: int = 32,
              w_cap: int = 0) -> int:
    """The tile for one kernel dispatch, resolved through the roofline
    autotuner (explicit table → VMEM/HBM model pick → the kernel's static
    default).  For the paged-read kernels the tile is the number of reads
    per grid step (see :func:`read_block`); ``kmer_histogram`` reads it as
    symbols per step.  Rounding ``n`` into pow2 buckets happens inside
    the table so jit program counts stay bounded."""
    from repro.roofline import autotune

    return autotune.tile_for(kernel, backend=jax.default_backend(),
                             bits=dtype_bits, n=n, w_cap=w_cap)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def srl(x: jax.Array, s) -> jax.Array:
    """Logical right shift of int32 lanes (the kernels keep words as
    int32 bit patterns)."""
    return lax.shift_right_logical(x, jnp.asarray(s, x.dtype))


def as_i32(value: int) -> int:
    """A uint32 bit pattern as the int32 Python constant with the same
    bits (kernel bodies hold words as int32)."""
    value &= 0xFFFFFFFF
    return value - (1 << 32) if value >= 1 << 31 else value


def read_block(n_reads: int, tile: int) -> tuple[int, int]:
    """``(R, n_pad)``: reads per grid step and the padded read count.

    ``n_pad`` is a multiple of ``R``; ``R`` is a multiple of 128 and
    either the whole padded count (one step) or a multiple of the SMEM
    tile (1024), so every block is legal for the TPU lowering."""
    n_pad = round_up(max(n_reads, 1), WAVE)
    block = min(round_up(max(tile, 1), SMEM_TILE), MAX_BLOCK)
    if n_pad <= block:
        return n_pad, n_pad
    return block, round_up(n_pad, block)


def stage_rows(words: jax.Array, halo: int) -> tuple[jax.Array, int]:
    """Stage a 1-D word stream as ``(n_rows, 128)`` int32 rows.

    Pads with the last word (the terminal continues past the end of S)
    so that rows ``r .. r + halo`` exist for every ``r`` that holds an
    in-contract word.  Returns ``(rows, n_rows)``."""
    n = words.shape[0]
    n_rows = -(-n // LANES) + halo + 1
    flat = jnp.full((n_rows * LANES,), words[-1], jnp.int32)
    flat = lax.dynamic_update_slice(flat, words.astype(jnp.int32), (0,))
    return flat.reshape(n_rows, LANES), n_rows


def pack_bytes(s_padded: jax.Array) -> jax.Array:
    """A terminal-padded byte-code string as big-endian 8-bit words (4
    symbols per int32), ending in a word of the last symbol, so that
    padding by the last word reads on as ``core.packing.gather_pack``
    clamps: the staged form of the byte-string kernels, whose aligned
    word reads ARE the byte sort keys."""
    n = s_padded.shape[0]
    sym = jnp.full((round_up(n, 4) + 4,), s_padded[-1], jnp.int32)
    sym = lax.dynamic_update_slice(sym, s_padded.astype(jnp.int32), (0,))
    g = sym.reshape(-1, 4) & 0xFF
    return (g[:, 0] << 24) | (g[:, 1] << 16) | (g[:, 2] << 8) | g[:, 3]


def n_windows(nw: int) -> int:
    """128-lane chunks covering ``nw + 1`` words (the extra word feeds
    the sub-word funnel shift)."""
    return -(-(nw + 1) // LANES)


def read_windows(word0_ref, s_hbm, win, sem, u_scr, ut_ref, *,
                 n_rows: int, nwin: int) -> None:
    """Fill ``ut_ref[j, r]`` with word ``word0[r] + j`` of the staged rows,
    for ``j < nwin * 128`` and every read ``r`` of this grid step.

    ``win``: VMEM ``(2, WAVE * (nwin + 1), 128)`` DMA landing slots;
    ``sem``: two DMA semaphores (one per slot); ``u_scr``: VMEM
    ``(nwin, WAVE, 128)`` aligned rows of one wave."""
    n_waves = ut_ref.shape[1] // WAVE
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def copy(wave, slot, r, c):
        row = jnp.clip(word0_ref[wave * WAVE + r] // LANES, 0,
                       n_rows - 1 - nwin)
        return pltpu.make_async_copy(
            s_hbm.at[row + c], win.at[slot, r * (nwin + 1) + c],
            sem.at[slot])

    def start_wave(wave, slot):
        def body(r, carry):
            for c in range(nwin + 1):
                copy(wave, slot, r, c).start()
            return carry
        lax.fori_loop(0, WAVE, body, 0)

    start_wave(0, 0)

    def wave_body(wave, carry):
        slot = wave % 2

        @pl.when(wave + 1 < n_waves)
        def _prefetch():
            start_wave(wave + 1, 1 - slot)

        def align(r, carry):
            for c in range(nwin + 1):
                copy(wave, slot, r, c).wait()
            first = word0_ref[wave * WAVE + r] % LANES
            shift = (LANES - first) % LANES
            rows = [win[slot, pl.ds(r * (nwin + 1) + c, 1), :]
                    for c in range(nwin + 1)]
            for c in range(nwin):
                lo = pltpu.roll(rows[c], shift, 1)
                hi = pltpu.roll(rows[c + 1], shift, 1)
                u_scr[c, pl.ds(r, 1), :] = jnp.where(lane < LANES - first,
                                                     lo, hi)
            return carry
        lax.fori_loop(0, WAVE, align, 0)
        col = pl.multiple_of(wave * WAVE, WAVE)
        for c in range(nwin):
            ut_ref[c * LANES:(c + 1) * LANES, pl.ds(col, WAVE)] = u_scr[c].T
        return carry
    lax.fori_loop(0, n_waves, wave_body, 0)


def aligned_words(ut_ref, off: jax.Array, lo: int, hi: int,
                  bits: int) -> jax.Array:
    """Words ``lo .. hi - 1`` of each read, shift-aligned to the symbol
    offsets ``off`` (a ``(1, R)`` row), as a ``(hi - lo, R)`` block: the
    funnel shift of word ``j`` with word ``j + 1`` by ``bits * (off %
    spw)`` bits.  ``(x >> 1) >> (31 - sh)`` equals ``x >> (32 - sh)`` for
    ``sh > 0`` and 0 at ``sh == 0``, keeping every shift in range."""
    sh = bits * (off % (32 // bits))
    u = ut_ref[lo:hi, :]
    u1 = ut_ref[lo + 1:hi + 1, :]
    return (u << sh) | srl(srl(u1, 1), 31 - sh)


def paged_call(body, rows: jax.Array, n_rows: int, *, spw: int, nw: int,
               starts, vecs=(), scalars=(), out_rows, tile: int,
               interpret: bool | None):
    """Run one paged-read kernel over every read.

    ``starts``: per-read SYMBOL offset arrays, shape ``(F,)`` — one
    window read each (``suffix_lcp`` reads two suffixes per row).
    ``vecs``: extra per-read int32 inputs in column form ``(k, F)``.
    ``scalars``: int32 scalars (SMEM).  ``out_rows``: the row count of
    each ``(rows, F)`` int32 output.  ``nw``: words read per window.

    ``body(scalars_ref, offs, uts, vec_refs, out_refs)`` sees ``offs`` as
    ``(1, R)`` symbol-offset rows and ``uts`` as the column-major window
    scratch of each start array (read it with :func:`aligned_words`).
    Returns the outputs sliced to ``(rows, F)``."""
    f = starts[0].shape[0]
    block, f_pad = read_block(f, tile)
    nwin = n_windows(nw)
    ns = len(starts)

    def pad(x):
        x = x.astype(jnp.int32)
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, f_pad - f)])

    offs = [pad(s)[None, :] for s in starts]
    word0 = [o[0] // spw for o in offs]
    vecs = [pad(v) for v in vecs]
    sc = jnp.stack([jnp.asarray(s, jnp.int32) for s in scalars] or
                   [jnp.int32(0)])

    def kernel(sc_ref, *refs):
        w0_refs = refs[:ns]
        off_refs = refs[ns:2 * ns]
        vec_refs = refs[2 * ns:2 * ns + len(vecs)]
        s_hbm = refs[2 * ns + len(vecs)]
        k = 2 * ns + len(vecs) + 1
        out_refs = refs[k:k + len(out_rows)]
        win, sem, u_scr, *uts = refs[k + len(out_rows):]
        for w0_ref, ut_ref in zip(w0_refs, uts):
            read_windows(w0_ref, s_hbm, win, sem, u_scr, ut_ref,
                         n_rows=n_rows, nwin=nwin)
        body(sc_ref, [r[...] for r in off_refs], uts, vec_refs, out_refs)

    smem_block = pl.BlockSpec((block,), lambda i: (i,),
                              memory_space=pltpu.SMEM)
    lanes_block = lambda k: pl.BlockSpec((k, block), lambda i: (0, i))
    outs = pl.pallas_call(
        kernel,
        grid=(f_pad // block,),
        in_specs=([pl.BlockSpec(memory_space=pltpu.SMEM)]
                  + [smem_block] * ns
                  + [lanes_block(1)] * ns
                  + [lanes_block(v.shape[0]) for v in vecs]
                  + [pl.BlockSpec(memory_space=pltpu.HBM)]),
        out_specs=[lanes_block(k) for k in out_rows],
        scratch_shapes=(
            [pltpu.VMEM((2, WAVE * (nwin + 1), LANES), jnp.int32),
             pltpu.SemaphoreType.DMA((2,)),
             pltpu.VMEM((nwin, WAVE, LANES), jnp.int32)]
            + [pltpu.VMEM((nwin * LANES + 8, block), jnp.int32)] * ns),
        out_shape=[jax.ShapeDtypeStruct((k, f_pad), jnp.int32)
                   for k in out_rows],
        interpret=default_interpret(interpret),
    )(sc, *word0, *offs, *vecs, rows)
    return [o[:, :f] for o in outs]


def per_read(call, text, *reads):
    """``call(text, *reads)`` for arrays whose leading axis is the read
    axis, with a batching rule that folds any vmapped axes into the read
    axis: one kernel launch over every read of every batch element (the
    elastic step vmaps over virtual trees), never a batched grid."""

    @jax.custom_batching.custom_vmap
    def run(text, *reads):
        return call(text, *reads)

    @run.def_vmap
    def _batched(axis_size, in_batched, text, *reads):
        if any(jax.tree_util.tree_leaves(in_batched[0])):
            raise NotImplementedError("the string may not be vmapped")
        reads = [r if b else jnp.broadcast_to(r, (axis_size,) + r.shape)
                 for r, b in zip(reads, in_batched[1:])]
        out = run(text, *[r.reshape((-1,) + r.shape[2:]) for r in reads])
        out = jax.tree_util.tree_map(
            lambda o: o.reshape((axis_size, -1) + o.shape[1:]), out)
        return out, jax.tree_util.tree_map(lambda _: True, out)

    return run(text, *reads)

"""Dense-packed vs byte string pipeline: gather + probe traffic/throughput.

Paper §6.1 packs DNA at 2 bits/symbol to cut the memory traffic of the
bandwidth-bound construction/probe gathers.  This suite measures the two
hot primitives the dense representation accelerates, byte path vs packed
path over the SAME random DNA string:

* ``gather``  — the elastic-range read (``range_gather`` family): F
  offsets x w symbols into byte sort keys;
* ``probe``   — the query binary-search inner step (``pattern_probe``
  family): B masked suffix-vs-pattern verdicts.

PR 5 adds the WORD-COMPARE rows: the same primitives with dense uint32
words as the comparison currency (no byte repack at all) —
``gather_words`` (raw word sort keys), ``probe_words`` (k-bit pattern
words vs shifted text words) and the ``suffix_lcp`` pair (byte-key
repack vs XOR + count-leading-zeros).  Their speedups are measured
against the PR-4 byte-repack packed path, the regression budget CI
watches.

Each row's derived column records the STRING bytes a row of the gather
touches under each representation (``row_bytes``; the packed window is
``w*bits/8`` plus one uint32 halo) and the wall-clock speedup — the JSON
artifact tracks both so CI catches traffic or throughput regressions.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.core import packing
from repro.core.alphabet import DNA
from repro.kernels import ops as kops
from repro.kernels import ref as kref

W = 64          # symbols per gather row (a mid-build elastic range)
F = 65_536      # gather rows / probe batch per call
PAT_LEN = 16    # probe pattern length (symbols)


def _string(n: int) -> np.ndarray:
    rng = np.random.default_rng(0)
    s = rng.integers(0, 4, size=n, dtype=np.uint8)
    return np.concatenate([s, np.array([4], np.uint8)])


def run(quick: bool = True) -> None:
    # sized so the byte string spills cache while the packed words stay
    # resident — the regime the paper's traffic argument is about (a
    # genome is ~3 GB; any realistic serving corpus dwarfs L3)
    n = 32_000_000 if quick else 128_000_000
    s = _string(n)
    pt = packing.pack_text(s, DNA, extra=W + 8)
    sp = jnp.asarray(DNA.pad_string(s, extra=W + 8))
    rng = np.random.default_rng(1)
    offs = jnp.asarray(rng.integers(0, n, size=F).astype(np.int32))

    use_pallas = kops._use_pallas()
    gather = jax.jit(lambda st, o: kops.range_gather_impl(use_pallas)(st, o, W))

    def timed(fn, *args):
        # best-of-9: single-digit repeats leave ±40% jitter on shared
        # hosts, which drowns the row-vs-row speedups this suite reports
        return timeit(lambda: jax.block_until_ready(fn(*args)),
                      repeats=9, warmup=2)

    # --- gather: F x W symbols -> byte sort keys ---------------------------
    t_byte = timed(gather, sp, offs)
    t_packed = timed(gather, pt, offs)
    byte_row = W
    packed_row = (-(-W // pt.syms_per_word) + 1) * 4
    emit("packed/gather_byte", t_byte,
         f"n={n} f={F} w={W} row_bytes={byte_row}")
    emit("packed/gather_dense", t_packed,
         f"n={n} f={F} w={W} row_bytes={packed_row} "
         f"bytes_ratio={byte_row / packed_row:.2f}x "
         f"speedup={t_byte / max(t_packed, 1e-9):.2f}x")

    # --- probe: B masked suffix-vs-pattern verdicts ------------------------
    # real-symbol patterns only (codes < terminal): the workload every
    # probe variant serves — terminal-bearing patterns are degenerate and
    # route to the byte fallback in production, so benchmarking them
    # against the word row would compare different work
    m_pad = -(-PAT_LEN // 4) * 4
    sym = rng.integers(0, 4, size=(F, m_pad)).astype(np.int32)
    lengths = rng.integers(1, PAT_LEN + 1, size=F)
    valid = np.arange(m_pad)[None, :] < lengths[:, None]
    pat = jnp.asarray(np.asarray(kref.pack_words_ref(
        jnp.asarray(np.where(valid, sym, 0)))))
    mask = jnp.asarray(np.asarray(kref.pack_words_ref(
        jnp.asarray(np.where(valid, 0xFF, 0)))))
    probe = jax.jit(lambda st, p: kops.pattern_probe_impl(use_pallas)(
        st, p, pat, mask))
    pos = jnp.asarray(rng.integers(0, n, size=F).astype(np.int32))

    t_byte_p = timed(probe, sp, pos)
    t_packed_p = timed(probe, pt, pos)
    byte_probe = m_pad
    packed_probe = (-(-m_pad // pt.syms_per_word) + 1) * 4
    emit("packed/probe_byte", t_byte_p,
         f"n={n} b={F} m={m_pad} row_bytes={byte_probe}")
    emit("packed/probe_dense", t_packed_p,
         f"n={n} b={F} m={m_pad} row_bytes={packed_probe} "
         f"bytes_ratio={byte_probe / packed_probe:.2f}x "
         f"speedup={t_byte_p / max(t_packed_p, 1e-9):.2f}x")

    # --- combined gather+probe (the serving hot loop mix) ------------------
    t_byte_gp = t_byte + t_byte_p
    t_packed_gp = t_packed + t_packed_p
    nominal = 8 / DNA.dense_bits
    emit("packed/gather_probe_total", t_packed_gp,
         f"byte_total_us={t_byte_gp * 1e6:.1f} "
         f"speedup={t_byte_gp / max(t_packed_gp, 1e-9):.2f}x "
         f"stored_bits={DNA.dense_bits} nominal_bytes_ratio={nominal:.0f}x")

    # --- WORD-COMPARE rows: dense words as the comparison currency ---------
    # speedups are vs the PR-4 byte-repack packed path above (the word
    # path's baseline), not vs the unpacked byte string.
    bits = pt.bits
    spw = pt.syms_per_word

    # gather_words: raw uint32 word sort keys, never spread back to bytes
    gather_w = jax.jit(lambda st, o: kops.range_gather_words_impl(
        use_pallas)(st, o, W))
    t_words_g = timed(gather_w, pt, offs)
    emit("packed/gather_words", t_words_g,
         f"n={n} f={F} w={W} key_words={-(-W // spw)} "
         f"vs_byte_keys={W // 4} "
         f"speedup={t_packed / max(t_words_g, 1e-9):.2f}x")

    # probe_words: k-bit pattern words vs shifted text words directly
    pat_sym = jnp.asarray(np.where(valid, sym, 0))
    pat_d = packing.pack_pattern_dense(pat_sym, bits, pt.terminal)
    mask_d = packing.pack_dense(
        jnp.asarray(np.where(valid, (1 << bits) - 1, 0)), bits)
    len_arr = jnp.asarray(lengths.astype(np.int32))
    probe_w = jax.jit(lambda st, p: kops.pattern_probe_words_impl(
        use_pallas)(st, p, pat_d, mask_d, len_arr))
    t_words_p = timed(probe_w, pt, pos)
    emit("packed/probe_words", t_words_p,
         f"n={n} b={F} m={m_pad} pat_words={pat_d.shape[1]} "
         f"vs_byte_words={m_pad // 4} "
         f"speedup={t_packed_p / max(t_words_p, 1e-9):.2f}x")

    # suffix-pair LCP: byte-key repack + row-LCP (PR 4) vs first
    # differing word + count-leading-zeros (PR 5)
    pos_b2 = jnp.asarray(rng.integers(0, n, size=F).astype(np.int32))
    gather = kops.range_gather_impl(use_pallas)
    lcp_bytekeys = jax.jit(lambda st, a, b: kref.lcp_pairs_ref(
        gather(st, a, W), gather(st, b, W), W)[0])
    if use_pallas:
        from repro.kernels.packed_gather import suffix_lcp_words

        lcp_words_fn = jax.jit(
            lambda st, a, b: suffix_lcp_words(st, a, b, W))
    else:
        lcp_words_fn = jax.jit(
            lambda st, a, b: kref.suffix_lcp_words_ref(st, a, b, W))
    t_lcp_byte = timed(lcp_bytekeys, pt, pos, pos_b2)
    t_lcp_words = timed(lcp_words_fn, pt, pos, pos_b2)
    emit("packed/suffix_lcp_bytekeys", t_lcp_byte, f"n={n} b={F} w={W}")
    emit("packed/suffix_lcp_words", t_lcp_words,
         f"n={n} b={F} w={W} "
         f"speedup={t_lcp_byte / max(t_lcp_words, 1e-9):.2f}x")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()

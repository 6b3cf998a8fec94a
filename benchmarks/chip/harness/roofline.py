"""Bytes the algorithm needs, for the roofline shares of the read kernels.

A read of ``w`` symbols needs those symbols at the alphabet's packed width,
its 4-byte offset, and what it writes.  The pages an implementation moves
to serve it are not counted, so the yardstick reads the same work whatever
implements it.  All reads are bound by HBM bandwidth (no arithmetic to
speak of), so the least time is bytes over the tabled HBM peak.
"""

from __future__ import annotations

OFFSET_BYTES = 4


def packed_bits(alphabet_size: int) -> int:
    """Stored bits per symbol: the smallest of 2, 4, 8 that holds the
    real letters (the terminal is implied by the text's length)."""
    need = max(1, (max(2, alphabet_size) - 1).bit_length())
    for bits in (2, 4, 8):
        if bits >= need:
            return bits
    return 8


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def gather_bytes(reads: int, w: int, bits: int) -> int:
    """Sort-key reads of the elastic loop: ``w`` symbols in, the same
    symbols out as packed 32-bit key words."""
    per = (_ceil_div(w * bits, 8) + OFFSET_BYTES
           + 4 * _ceil_div(w * bits, 32))
    return reads * per


def probe_bytes(probes: int, pattern_symbols: int, bits: int) -> int:
    """Binary-search probes: each reads the pattern's length of text at a
    suffix (its offset from the suffix array) and writes a 4-byte verdict.
    ``pattern_symbols`` is summed over the probes."""
    return (_ceil_div(pattern_symbols * bits, 8)
            + probes * (OFFSET_BYTES + 4))


def search_probes(n_leaves: int) -> int:
    """Probes per bound of a binary search over ``n_leaves`` suffixes."""
    return max(1, (n_leaves).bit_length()) + 1


def share(bytes_needed: float, hbm_bw: float, seconds: float) -> float | None:
    """Least time over measured time, in percent; None without a time."""
    if not seconds or seconds <= 0 or bytes_needed <= 0:
        return None
    return 100.0 * (bytes_needed / hbm_bw) / seconds

"""Jit'd dispatch wrappers for the ERA Pallas kernels.

On a real TPU the kernels run compiled (``interpret=False``); on CPU they
run in interpret mode for validation, and the pure-jnp reference path is
the default for speed.  Selection:

* ``REPRO_KERNELS=pallas``    — always use the Pallas kernels (interpret
                                 mode off-TPU);
* ``REPRO_KERNELS=jnp`` (default on CPU) — pure-jnp reference path;
* on TPU platforms the Pallas path is the default.

String-representation dispatch: every wrapper that reads the string
accepts EITHER the terminal-padded byte array (uint8 codes) OR a dense
k-bit :class:`repro.core.packing.PackedText`; the packed variants emit
byte-identical sort keys / verdicts (see :mod:`repro.kernels.packed_gather`),
so callers switch representation without touching results.

Comparison-currency dispatch: for a PackedText the hot comparisons
(suffix LCP, probe, the elastic-range sort keys) default to WORD-compare
— k-bit dense uint32 words compared directly, ``8/bits``x fewer compare
lanes — with the PR-4 byte-repack path kept as the oracle.
``REPRO_WORD_COMPARE=byte`` forces the byte-key path (bit-identical
results either way; tests pin it).
"""

from __future__ import annotations

import os
import threading

import jax

from repro import obs
from repro.core.packing import PackedText
from repro.kernels import ref as _ref
from repro.kernels.kmer_histogram import kmer_histogram as _kmer_pallas
from repro.kernels.lcp import lcp_pairs as _lcp_pallas
from repro.kernels.packed_gather import (
    pattern_probe_packed as _packed_probe_pallas,
    pattern_probe_words as _words_probe_pallas,
    range_gather_packed as _packed_gather_pallas,
    range_gather_words as _words_gather_pallas,
    suffix_lcp_words as _words_lcp_pallas,
)
from repro.kernels.pattern_probe import pattern_probe as _probe_pallas
from repro.kernels.probe_gather import (
    probe_gather_packed as _fused_packed_pallas,
    probe_gather_words as _fused_words_pallas,
)
from repro.kernels.range_gather import range_gather_pack as _gather_pallas
from repro.kernels.suffix_lcp import suffix_lcp_pairs as _suffix_lcp_pallas
from repro.kernels import tiles as _tiles


# ---------------------------------------------------------------------------
# Kernel-trace telemetry (REPRO_METRICS / REPRO_TRACE).  The record helper
# runs in the impl closures' Python bodies: under jit that is TRACE time, so
# ``kernel_traces_total`` counts jit traces per distinct padded shape — the
# jit-cache pressure signal the serving/bench layers need — not executions
# (eager callers trace on every call).  ``kernel_distinct_shapes_total`` is
# the recompile proxy: it grows only when a (kernel, currency, shape) triple
# is first seen.  Kernel device time comes from a profiler trace, not here.
# ---------------------------------------------------------------------------

_SHAPES_SEEN: set[tuple] = set()
_SHAPES_LOCK = threading.Lock()


def _record(kernel: str, use_pallas: bool, currency: str, *arrays,
            tile: int = 0) -> None:
    if not use_pallas:
        impl = "ref"
    else:
        impl = "interpret" if _tiles.default_interpret(None) else "pallas"
    if obs.trace_enabled():
        rows = int(arrays[0].shape[0]) if arrays else 0
        obs.tracer().instant(f"kernel/{kernel}/trace", kernel=kernel,
                             impl=impl, currency=currency, rows=rows,
                             tile=tile)
    if not obs.metrics_enabled():
        return
    m = obs.metrics()
    m.counter("kernel_traces_total",
              "kernel impl jit traces (one per traced padded shape under "
              "jit, one per call when eager; not executions)",
              kernel=kernel, impl=impl, currency=currency).inc()
    shape = tuple(tuple(getattr(a, "shape", ())) for a in arrays)
    key = (kernel, currency, shape)
    with _SHAPES_LOCK:
        new = key not in _SHAPES_SEEN
        if new:
            _SHAPES_SEEN.add(key)
    if new:
        m.counter("kernel_distinct_shapes_total",
                  "distinct padded argument shapes per kernel "
                  "(jit-recompile proxy)",
                  kernel=kernel, currency=currency).inc()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas() -> bool:
    env = os.environ.get("REPRO_KERNELS", "")
    if env == "pallas":
        return True
    if env == "jnp":
        return False
    return _on_tpu()


def _use_word_compare() -> bool:
    """Word-compare is the default for dense-packed strings;
    ``REPRO_WORD_COMPARE=byte`` pins the PR-4 byte-repack oracle path.
    Resolved OUTSIDE jitted traces (a static arg), like ``_use_pallas``."""
    env = os.environ.get("REPRO_WORD_COMPARE", "")
    if env == "byte":
        return False
    if env in ("", "word"):
        return True
    raise ValueError(
        f"unknown REPRO_WORD_COMPARE={env!r}; choose 'word' or 'byte'")


def _use_sort_fuse() -> bool:
    """Fused single-lane sort keys are the default construction currency
    (PR-8 promoted engine); ``REPRO_SORT=lexsort`` pins the three-lane
    lexsort oracle path.  Resolved OUTSIDE jitted traces (a static arg),
    like ``_use_pallas``/``_use_word_compare``."""
    env = os.environ.get("REPRO_SORT", "")
    if env == "lexsort":
        return False
    if env in ("", "fused"):
        return True
    raise ValueError(
        f"unknown REPRO_SORT={env!r}; choose 'fused' or 'lexsort'")


def _use_compaction() -> bool:
    """Tail compaction (sort only still-active rows) is the default for
    the batched/streaming/append host loops; ``REPRO_COMPACT=off`` pins
    the full-width oracle path.  Resolved OUTSIDE jitted traces."""
    env = os.environ.get("REPRO_COMPACT", "")
    if env == "off":
        return False
    if env in ("", "tail"):
        return True
    raise ValueError(
        f"unknown REPRO_COMPACT={env!r}; choose 'tail' or 'off'")


def _tile(kernel: str, s_text, w: int = 0) -> int:
    """Autotuned tile for one dispatch — resolved at trace time from
    STATIC shapes only (``PackedText.words``/byte-array length), so the
    choice is a jit-cache key, never a traced value."""
    if isinstance(s_text, PackedText):
        n = s_text.words.shape[0] * (32 // s_text.bits)
        bits = s_text.bits
    else:
        n = int(s_text.shape[0])
        bits = 32
    return _tiles.pick_tile(kernel, n=n, dtype_bits=bits, w_cap=w)


def range_gather_impl(use_pallas: bool):
    """Gather-and-pack implementation for a STATIC ``use_pallas`` —
    returns ``fn(s_text, offs, w) -> (F, w//4) int32`` byte sort keys,
    dispatching on the string representation inside the trace."""
    def fn(s_text, offs, w: int):
        tile = _tile("range_gather", s_text, w)
        if isinstance(s_text, PackedText):
            _record("range_gather", use_pallas, "packed", offs, tile=tile)
            if use_pallas:
                return _packed_gather_pallas(s_text, offs, w, tile=tile)
            return _ref.range_gather_packed_ref(s_text, offs, w)
        _record("range_gather", use_pallas, "byte", offs, tile=tile)
        if use_pallas:
            return _gather_pallas(s_text, offs, w, tile=tile)
        return _ref.range_gather_pack_ref(s_text, offs, w)
    return fn


def range_gather_pack(s_text, offs, w: int):
    return range_gather_impl(_use_pallas())(s_text, offs, w)


def kmer_histogram(s_padded, n: int, k: int, base: int):
    use_pallas = _use_pallas()
    tile = _tile("kmer_histogram", s_padded, k)
    _record("kmer_histogram", use_pallas, "byte", s_padded, tile=tile)
    if use_pallas:
        return _kmer_pallas(s_padded, n, k, base, tile=tile)
    return _ref.kmer_histogram_ref(s_padded, n, k, base)


def range_gather_words_impl(use_pallas: bool):
    """Word-key gather for a STATIC ``use_pallas``: ``fn(pt, offs, w) ->
    (F, ceil(w/spw)) uint32`` substituted dense word rows (PackedText
    only — the word currency has no byte-string form)."""
    def fn(pt: PackedText, offs, w: int):
        tile = _tile("range_gather_words", pt, w)
        _record("range_gather", use_pallas, "word", offs, tile=tile)
        if use_pallas:
            return _words_gather_pallas(pt, offs, w, tile=tile)
        return _ref.range_gather_words_ref(pt, offs, w)
    return fn


def range_gather_words(pt: PackedText, offs, w: int):
    return range_gather_words_impl(_use_pallas())(pt, offs, w)


def suffix_lcp_pairs(s_text, pos_a, pos_b, w: int):
    tile = _tile("suffix_lcp", s_text, w)
    if isinstance(s_text, PackedText):
        if _use_word_compare():
            # word path: first differing dense word + clz, no byte repack
            _record("suffix_lcp", _use_pallas(), "word", pos_a, tile=tile)
            if _use_pallas():
                return _words_lcp_pallas(s_text, pos_a, pos_b, w, tile=tile)
            return _ref.suffix_lcp_words_ref(s_text, pos_a, pos_b, w)
        # byte-key oracle path: two byte-key gathers feed the shared
        # row-LCP — identical to the byte kernel's symbol scan.
        gather = range_gather_impl(_use_pallas())
        a = gather(s_text, pos_a, w)
        b = gather(s_text, pos_b, w)
        return lcp_pairs(a, b, w)[0]
    _record("suffix_lcp", _use_pallas(), "byte", pos_a, tile=tile)
    if _use_pallas():
        return _suffix_lcp_pallas(s_text, pos_a, pos_b, w, tile=tile)
    return _ref.suffix_lcp_pairs_ref(s_text, pos_a, pos_b, w)


def lcp_pairs(a, b, w: int):
    _record("lcp_pairs", _use_pallas(), "byte", a)
    if _use_pallas():
        return _lcp_pallas(a, b, w)
    return _ref.lcp_pairs_ref(a, b, w)


def pattern_probe_impl(use_pallas: bool):
    """Probe implementation for a STATIC ``use_pallas`` — jitted callers
    (repro.core.query / analytics) resolve the env var once outside the
    trace so flipping REPRO_KERNELS between calls cannot hit a stale
    trace; the byte-vs-packed branch dispatches on the s_text type."""
    def fn(s_text, pos, pat_words, mask_words):
        w = pat_words.shape[1] * 4
        tile = _tile("pattern_probe", s_text, w)
        if isinstance(s_text, PackedText):
            _record("pattern_probe", use_pallas, "packed", pos, pat_words,
                    tile=tile)
            if use_pallas:
                return _packed_probe_pallas(s_text, pos, pat_words,
                                            mask_words, tile=tile)
            return _ref.pattern_probe_packed_ref(s_text, pos, pat_words,
                                                 mask_words)
        _record("pattern_probe", use_pallas, "byte", pos, pat_words,
                tile=tile)
        if use_pallas:
            return _probe_pallas(s_text, pos, pat_words, mask_words,
                                 tile=tile)
        return _ref.pattern_probe_ref(s_text, pos, pat_words, mask_words)
    return fn


def pattern_probe(s_text, pos, pat_words, mask_words):
    return pattern_probe_impl(_use_pallas())(s_text, pos, pat_words, mask_words)


def pattern_probe_words_impl(use_pallas: bool):
    """Word-compare probe for a STATIC ``use_pallas``:
    ``fn(pt, pos, pat_dense, mask_dense, lengths, lim_p=None) -> int32[B]``
    verdicts (PackedText only; patterns must be real-symbol apart from a
    terminal-padded tail described by ``lim_p`` — callers fall back to
    :func:`pattern_probe_impl` for other terminal-bearing batches)."""
    def fn(pt: PackedText, pos, pat_dense, mask_dense, lengths, lim_p=None):
        w = pat_dense.shape[1] * (32 // pt.bits)
        tile = _tile("pattern_probe_words", pt, w)
        _record("pattern_probe", use_pallas, "word", pos, pat_dense,
                tile=tile)
        if use_pallas:
            return _words_probe_pallas(pt, pos, pat_dense, mask_dense,
                                       lengths, lim_p, tile=tile)
        return _ref.pattern_probe_words_ref(pt, pos, pat_dense, mask_dense,
                                            lengths, lim_p)
    return fn


def pattern_probe_words(pt: PackedText, pos, pat_dense, mask_dense, lengths,
                        lim_p=None):
    return pattern_probe_words_impl(_use_pallas())(pt, pos, pat_dense,
                                                   mask_dense, lengths, lim_p)


def probe_gather_words_impl(use_pallas: bool):
    """Fused find-and-fetch (word currency) for a STATIC ``use_pallas``:
    ``fn(pt, pos, pat_dense, mask_dense, lengths, fetch, lim_p=None) ->
    (cmp int32[B], win uint32[B, ceil(fetch/spw)])`` — one launch for the
    probe verdict AND the gathered dense word window (PackedText only)."""
    def fn(pt: PackedText, pos, pat_dense, mask_dense, lengths, fetch: int,
           lim_p=None):
        w = max(pat_dense.shape[1] * (32 // pt.bits), fetch)
        tile = _tile("probe_gather_words", pt, w)
        _record("probe_gather", use_pallas, "word", pos, pat_dense,
                tile=tile)
        if use_pallas:
            return _fused_words_pallas(pt, pos, pat_dense, mask_dense,
                                       lengths, lim_p, fetch=fetch,
                                       tile=tile)
        return _ref.probe_gather_words_ref(pt, pos, pat_dense, mask_dense,
                                           lengths, lim_p, fetch=fetch)
    return fn


def probe_gather_words(pt: PackedText, pos, pat_dense, mask_dense, lengths,
                       fetch: int, lim_p=None):
    return probe_gather_words_impl(_use_pallas())(pt, pos, pat_dense,
                                                  mask_dense, lengths, fetch,
                                                  lim_p)


def probe_gather_impl(use_pallas: bool):
    """Fused find-and-fetch (byte-key currency) for a STATIC ``use_pallas``:
    ``fn(s_text, pos, pat_words, mask_words, fetch) ->
    (cmp int32[B], keys int32[B, fetch//4])``.

    Dense strings run the fused packed kernel / ref; a plain byte string
    has no fused kernel — it runs the literal two-launch probe→gather
    composition (which is also the fused kernels' semantic definition, so
    results are interchangeable across representations)."""
    def fn(s_text, pos, pat_words, mask_words, fetch: int):
        if isinstance(s_text, PackedText):
            w = max(pat_words.shape[1] * 4, fetch)
            tile = _tile("probe_gather", s_text, w)
            _record("probe_gather", use_pallas, "packed", pos, pat_words,
                    tile=tile)
            if use_pallas:
                return _fused_packed_pallas(s_text, pos, pat_words,
                                            mask_words, fetch=fetch,
                                            tile=tile)
            return _ref.probe_gather_packed_ref(s_text, pos, pat_words,
                                                mask_words, fetch=fetch)
        cmp = pattern_probe_impl(use_pallas)(s_text, pos, pat_words,
                                             mask_words)
        win = range_gather_impl(use_pallas)(s_text, pos, fetch)
        return cmp, win
    return fn


def probe_gather(s_text, pos, pat_words, mask_words, fetch: int):
    return probe_gather_impl(_use_pallas())(s_text, pos, pat_words,
                                            mask_words, fetch)

"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is attached: ``jax.experimental.topologies`` describes a v5e 2x2
slice and each test lowers and compiles one kernel, or the jitted elastic
step, for its first device at the widths the system runs.  What the TPU
compiler refuses here — block shapes, unaligned slices, too much VMEM or
HBM — would fail on the chip.  Nothing runs, so these tests say nothing
about results or times: the interpret-mode parity tests and
``chip_smoke.py`` cover those.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import packing
from repro.core.alphabet import BYTE, DNA
from repro.core.prepare import (
    PrepareState,
    _jit_compact_step_batch,
    _jit_step_batch,
)
from repro.kernels import tiles
from repro.kernels.kmer_histogram import kmer_histogram
from repro.kernels.lcp import lcp_pairs
from repro.kernels.packed_gather import (
    pattern_probe_packed,
    pattern_probe_words,
    range_gather_packed,
    range_gather_words,
    suffix_lcp_words,
)
from repro.kernels.pattern_probe import pattern_probe
from repro.kernels.probe_gather import probe_gather_packed, probe_gather_words
from repro.kernels.range_gather import range_gather_pack
from repro.kernels.suffix_lcp import suffix_lcp_pairs

HBM_BYTES = 16 << 30   # one v5e chip
N = 1 << 25            # text symbols (chip_smoke.py's default size)
F = 1 << 20            # reads per kernel call
PAT = 32               # query pattern symbols
# chip_smoke.py's prepare state: 13 virtual trees of <= 3,728,270 leaves
SMOKE_G, SMOKE_F = 13, 3_728_270


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it, and
    # drop traces other tests made with interpreted kernels.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def spec(shape, dtype, dev):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)


def dense_text(alpha, dev) -> packing.PackedText:
    spw = 32 // alpha.dense_bits
    n_words = -(-(N + 520) // spw) + 1  # pack_text's extra=2*w_max+8 tail
    return packing.PackedText(words=spec((n_words,), jnp.uint32, dev),
                              n_real=spec((), jnp.int32, dev),
                              bits=alpha.dense_bits,
                              terminal=alpha.terminal_code)


def compile_on(fn, *args):
    """Compile ``fn`` for the described chip; check that it holds a Pallas
    kernel and fits one chip's HBM."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used <= HBM_BYTES, used
    return compiled


@pytest.mark.parametrize("w", [64, 256])
def test_range_gather_words(chip, w):
    compile_on(lambda pt, o: range_gather_words(pt, o, w, interpret=False),
               dense_text(DNA, chip), spec((F,), jnp.int32, chip))


def probe_args(alpha, dev):
    nw = -(-PAT // (32 // alpha.dense_bits))
    return (dense_text(alpha, dev), spec((F,), jnp.int32, dev),
            spec((F, nw), jnp.uint32, dev), spec((F, nw), jnp.uint32, dev),
            spec((F,), jnp.int32, dev))


@pytest.mark.parametrize("alpha", [DNA, BYTE], ids=lambda a: a.name)
def test_pattern_probe_words(chip, alpha):
    compile_on(lambda *a: pattern_probe_words(*a, interpret=False),
               *probe_args(alpha, chip))


@pytest.mark.parametrize("alpha", [DNA, BYTE], ids=lambda a: a.name)
def test_probe_gather_words(chip, alpha):
    compile_on(lambda *a: probe_gather_words(*a, fetch=64, interpret=False),
               *probe_args(alpha, chip))


@pytest.mark.parametrize("alpha", [DNA, BYTE], ids=lambda a: a.name)
def test_suffix_lcp_words(chip, alpha):
    compile_on(lambda pt, a, b: suffix_lcp_words(pt, a, b, 256,
                                                 interpret=False),
               dense_text(alpha, chip), spec((F,), jnp.int32, chip),
               spec((F,), jnp.int32, chip))


def test_byte_key_family(chip):
    """The byte-key kernels over dense DNA (the oracle currency)."""
    pt, pos = dense_text(DNA, chip), spec((F,), jnp.int32, chip)
    rows = spec((F, PAT // 4), jnp.int32, chip)
    compile_on(lambda pt, o: range_gather_packed(pt, o, 64, interpret=False),
               pt, pos)
    compile_on(lambda *a: pattern_probe_packed(*a, interpret=False),
               pt, pos, rows, rows)
    compile_on(lambda *a: probe_gather_packed(*a, fetch=64, interpret=False),
               pt, pos, rows, rows)


def test_byte_string_kernels(chip):
    """The kernels over a one-byte-per-symbol string (byte alphabets)."""
    s, pos = spec((N + 520,), jnp.uint8, chip), spec((F,), jnp.int32, chip)
    rows = spec((F, PAT // 4), jnp.int32, chip)
    compile_on(lambda s, o: range_gather_pack(s, o, 64, interpret=False),
               s, pos)
    compile_on(lambda *a: pattern_probe(*a, interpret=False), s, pos, rows,
               rows)
    compile_on(lambda s, a, b: suffix_lcp_pairs(s, a, b, 64,
                                                interpret=False),
               s, pos, pos)


@pytest.mark.parametrize("k", [3, 6])
def test_kmer_histogram(chip, k):
    compile_on(lambda s: kmer_histogram(s, N, k, DNA.base, interpret=False),
               spec((N + 8,), jnp.uint8, chip))


def test_lcp_pairs(chip):
    rows = spec((F, 16), jnp.int32, chip)
    compile_on(lambda a, b: lcp_pairs(a, b, 64, interpret=False), rows, rows)


@pytest.mark.parametrize("f_prime", [1 << 20, None],
                         ids=["compacted", "full-width"])
def test_elastic_step(chip, f_prime, monkeypatch):
    """The jitted elastic step at chip_smoke.py's (G, F), compacted to the
    widest bucket ``compaction_width`` allows there (the default engine)
    and full width (what the smoke runs), with the kernels compiled (the
    default interpret policy sees this CPU host): it holds the gather
    kernel and fits one chip's HBM."""
    monkeypatch.setattr(tiles, "default_interpret",
                        lambda interpret: bool(interpret))
    grid = spec((SMOKE_G, SMOKE_F), jnp.int32, chip)
    states = PrepareState(*([grid] * 6))
    step_kw = dict(use_pallas=True, word_keys=True, sort_fuse=True)
    if f_prime is None:
        step = lambda s, st: _jit_step_batch(s, st, 16, **step_kw)
    else:
        step = lambda s, st: _jit_compact_step_batch(
            s, st, 16, f_prime=f_prime, **step_kw)
    compile_on(step, dense_text(DNA, chip), states)

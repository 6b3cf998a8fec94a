"""The benchmark's inputs repeat exactly from a seed, and the reference
agrees with a brute-force one."""

import json

import numpy as np
import pytest

import chipbench_util  # noqa: F401  (puts the harness on sys.path)
from harness import data, reference, roofline, spec

BIG_SEED = 3_141_592_653_589  # more than 32 signed bits hold


def _config(name, n=5000):
    cfg = json.loads(spec.config_file(name).read_text())
    cfg["n"] = n
    f_max = (n + 1) // (cfg["min_groups"] + 1)
    cfg["era"]["memory_bytes"] = -(-f_max * 2 * 16 * 5 // 3)
    cfg["era"]["r_bytes"] = 16 * (n + 1)
    return cfg


@pytest.mark.parametrize("name", ["dna_chr", "swissprot"])
@pytest.mark.parametrize("seed", [0, 7, BIG_SEED, -5])
def test_text_repeats_from_seed(name, seed):
    cfg = _config(name)
    a, b = data.make_text(cfg, seed), data.make_text(cfg, seed)
    assert np.array_equal(a, b)
    assert a.dtype == np.uint8 and len(a) == cfg["n"] + 1
    assert a[-1] == len(cfg["symbols"]) and a[:-1].max() < len(cfg["symbols"])
    others = [data.make_text(cfg, seed + d) for d in range(1, 6)]
    assert not all(np.array_equal(a, o) for o in others)


@pytest.mark.parametrize("name", ["dna_chr", "swissprot"])
def test_every_seed_gets_the_same_work(name):
    """A seed relabels the base text's letters: the same letter counts up
    to order, the same partition sizes (so the same compiled programs),
    and a different suffix order."""
    from harness import runs
    from repro.core.api import EraIndexer

    cfg = _config(name, n=30000)
    k = len(cfg["symbols"])
    texts = [data.make_text(cfg, s) for s in (1, 2)] + data.make_texts(
        cfg, 3, 3)
    counts = [np.sort(np.bincount(t, minlength=k + 1)) for t in texts]
    assert all(np.array_equal(counts[0], c) for c in counts[1:])
    ix = EraIndexer(runs.program_alphabet(cfg), runs.era_config(cfg))
    shapes = []
    for t in texts:
        groups = ix.partition(t)
        shapes.append((len(groups), ix._capacity(groups),
                       sorted(g.total_freq for g in groups)))
    assert all(sh == shapes[0] for sh in shapes)
    base = k + 1
    sas = [reference.suffix_array(t, base) for t in texts[:2]]
    assert not np.array_equal(sas[0], sas[1]) or np.array_equal(texts[0],
                                                               texts[1])


@pytest.mark.parametrize("k,count", [(4, 24), (20, 64)])
def test_a_runs_texts_are_distinct(k, count):
    """Each build of a run gets a relabelling no other build of the run
    had; the first is the seed's own, as ``make_text`` gives it."""
    perms = data.permutations(k, BIG_SEED, count)
    assert len({p.tobytes() for p in perms}) == count
    assert all(np.array_equal(np.sort(p), np.arange(k)) for p in perms)
    assert np.array_equal(perms[0], data.permutations(k, BIG_SEED, 1)[0])
    with pytest.raises(ValueError):
        data.permutations(4, 1, 25)


def test_protein_composition_is_swissprot_shaped():
    cfg = _config("swissprot", n=200_000)
    cfg["text"] = dict(cfg["text"], repeat_fraction=0.0)
    p = data.composition(cfg["text"], cfg["symbols"])
    freq = np.bincount(data.base_text(cfg)[:-1], minlength=20) / cfg["n"]
    assert np.abs(freq - p).max() < 0.003
    assert freq.argmax() == cfg["symbols"].index("L")
    # a run's relabelling gives each code one of those frequencies
    run = np.bincount(data.make_text(cfg, 11)[:-1], minlength=20) / cfg["n"]
    assert np.allclose(np.sort(run), np.sort(freq))


@pytest.mark.parametrize("seed", [3, BIG_SEED])
def test_seeds_repeat_from_seed(seed):
    cfg = _config("dna_chr", n=50_000)
    traffic = json.loads(spec.traffic_file("seeds").read_text())
    text = data.make_text(cfg, seed)
    a = data.make_seeds(text, traffic, seed, 300, 4)
    b = data.make_seeds(text, traffic, seed, 300, 4)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    lens = np.array([len(x) for x in a])
    assert lens.min() >= 19 and lens.max() <= 32
    assert all(x.max() < 4 for x in a)
    c = data.make_seeds(text, traffic, seed + 1, 300, 4)
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))


def _brute_sa(s):
    return np.array(sorted(range(len(s)), key=lambda i: bytes(s[i:])))


@pytest.mark.parametrize("alphabet,size", [("dna", 4), ("protein", 20)])
@pytest.mark.parametrize("seed", [1, 2])
def test_suffix_array_matches_brute_force(alphabet, size, seed):
    rng = np.random.default_rng(seed)
    body = rng.integers(0, size, 600).astype(np.uint8)
    body[100:164] = body[300:364]          # a long repeat
    s = np.concatenate([body, [size]]).astype(np.uint8)
    assert np.array_equal(reference.suffix_array(s, size + 1), _brute_sa(s))


def test_control_depth_breaks_suffix_order():
    rng = np.random.default_rng(4)
    body = rng.integers(0, 4, 2000).astype(np.uint8)
    motif = body[:64].copy()
    for p in (500, 900, 1300):
        body[p:p + 64] = motif
    s = np.concatenate([body, [4]]).astype(np.uint8)
    ref = reference.suffix_array(s, 5)
    ctl = reference.suffix_array(s, 5, depth=16)
    assert np.count_nonzero(ref != ctl) > 0
    assert np.array_equal(np.sort(ctl), np.arange(len(s)))


def test_occurrences_and_control():
    text = bytes([0, 1, 2, 0, 1, 2, 0, 1, 3])
    assert reference.occurrences(text, [0, 1, 2]) == [0, 3]
    assert reference.occurrences(text, [0, 1]) == [0, 3, 6]
    assert reference.occurrences(text, [0, 1, 2], depth=2) == [0, 3, 6]


def test_roofline_bytes():
    assert roofline.packed_bits(4) == 2
    assert roofline.packed_bits(20) == 8
    # w = 16 DNA symbols: 4 bytes in, 4-byte offset, one 4-byte key word
    assert roofline.gather_bytes(10, 16, 2) == 10 * 12
    # byte path: 16 bytes in, offset, four key words
    assert roofline.gather_bytes(1, 16, 8) == 16 + 4 + 16
    assert roofline.search_probes(2 ** 22 + 1) == 24
    assert roofline.share(819e9, 819e9, 2.0) == pytest.approx(50.0)
    assert roofline.share(1.0, 819e9, 0.0) is None

"""Inputs made from ``--seed``: the indexed text and the query stream.

The text generator follows the shape of the program's synthetic datasets
(a random background with one 64-symbol motif planted over a share of the
text, which gives the deep shared prefixes of real repeats), with its own
copy of the code so that a later change to the program cannot move the
yardstick.  A configuration may give a per-letter composition (Swiss-Prot's
amino-acid frequencies); without one the background is uniform.  The text
is drawn once from the configuration's ``base_seed``; ``--seed`` relabels
its letters (each build of a run has a relabelling of its own), and draws
the query stream.

Codes are ``0 .. |alphabet| - 1`` in the order of the configuration's
``symbols`` string, and the terminal is ``|alphabet|``, the largest code.
"""

from __future__ import annotations

import math

import numpy as np

# Which stream of random numbers each input draws from, so that the text
# and the traffic of one seed are independent and repeat exactly.
TEXT, TRAFFIC, SAMPLE, ARRIVALS = 0, 1, 2, 3


def rng_for(seed: int, stream: int, *more: int) -> np.random.Generator:
    """A generator for ``stream`` of ``seed`` (and any further keys, such as
    a build's index); any whole number is a seed."""
    return np.random.default_rng([int(seed) % (1 << 64), stream, *more])


def composition(text_cfg: dict, symbols: str) -> np.ndarray | None:
    """Per-code probabilities from ``{"composition": {letter: percent}}``,
    normalised, or None for a uniform background."""
    comp = text_cfg.get("composition")
    if not comp:
        return None
    if set(comp) != set(symbols):
        raise ValueError("composition must give every letter of the "
                         f"alphabet exactly once: {sorted(set(symbols) ^ set(comp))}")
    p = np.array([float(comp[c]) for c in symbols])
    return p / p.sum()


def base_text(config: dict) -> np.ndarray:
    """The configuration's text before relabelling: a terminated uint8
    code string drawn from the configuration's own ``base_seed``."""
    symbols = config["symbols"]
    n = int(config["n"])
    text_cfg = config["text"]
    k = len(symbols)
    rng = rng_for(int(text_cfg["base_seed"]), TEXT)
    p = composition(text_cfg, symbols)
    if p is None:
        base = rng.integers(0, k, size=n, dtype=np.uint8)
    else:
        base = rng.choice(k, size=n, p=p).astype(np.uint8)
    rep_len = int(text_cfg["repeat_len"])
    n_rep = int(n * float(text_cfg["repeat_fraction"]) / rep_len)
    if n_rep and n > 2 * rep_len:
        if p is None:
            motif = rng.integers(0, k, size=rep_len, dtype=np.uint8)
        else:
            motif = rng.choice(k, size=rep_len, p=p).astype(np.uint8)
        for q in rng.integers(0, n - rep_len, size=n_rep):
            base[q:q + rep_len] = motif
    return np.concatenate([base, np.array([k], np.uint8)])


def permutations(k: int, seed: int, count: int) -> list[np.ndarray]:
    """``count`` distinct permutations of ``k`` letters drawn from ``seed``
    (at most ``k!``): the first from the seed alone, each next from the
    seed and a running index, skipping any drawn before."""
    if count > math.factorial(k):
        raise ValueError(f"{k} letters have no {count} distinct orders")
    perms: list[np.ndarray] = []
    seen: set[bytes] = set()
    index = 0
    while len(perms) < count:
        more = (index,) if index else ()
        perm = rng_for(seed, TEXT, *more).permutation(k).astype(np.uint8)
        index += 1
        if perm.tobytes() not in seen:
            seen.add(perm.tobytes())
            perms.append(perm)
    return perms


def relabel(base: np.ndarray, perm: np.ndarray) -> np.ndarray:
    """``base`` with letter ``c`` written ``perm[c]``; the terminal
    ``len(perm)`` stays the largest code."""
    k = len(perm)
    return np.concatenate([perm, [k]]).astype(np.uint8)[base]


def make_texts(config: dict, seed: int, count: int) -> list[np.ndarray]:
    """``count`` texts of run ``seed``, the base text under distinct
    relabellings.  Every seed and every text gets the same work — the
    same partition sizes, the same longest common prefixes, the same
    elastic iterations, so the same compiled programs — and a suffix
    order of its own to check."""
    base = base_text(config)
    k = len(config["symbols"])
    return [relabel(base, p) for p in permutations(k, seed, count)]


def make_text(config: dict, seed: int) -> np.ndarray:
    """The first text of run ``seed``."""
    return make_texts(config, seed, 1)[0]


def make_seeds(text: np.ndarray, traffic: dict, seed: int, count: int,
               alphabet_size: int) -> list[np.ndarray]:
    """``count`` read seeds copied from uniform start positions of the
    text, with uniform lengths in ``seed_len`` and per-symbol substitutions
    at ``substitution_rate`` (each to one of the other letters).  Each is
    a read-only int32 view into one (count, longest) block."""
    rng = rng_for(seed, TRAFFIC)
    lo, hi = (int(v) for v in traffic["seed_len"])
    n = len(text) - 1
    lengths = rng.integers(lo, hi + 1, size=count)
    starts = rng.integers(0, n - hi + 1, size=count)
    win = text[starts[:, None] + np.arange(hi)[None, :]].astype(np.int32)
    sub = rng.random((count, hi)) < float(traffic["substitution_rate"])
    shift = rng.integers(1, alphabet_size, size=(count, hi))
    win = np.where(sub, (win + shift) % alphabet_size, win).astype(np.int32)
    win.flags.writeable = False
    return [win[i, :lengths[i]] for i in range(count)]

"""The plain reference: suffix order and pattern occurrences on the host.

It imports nothing of the program and takes nothing it made.  A suffix
array by prefix doubling over numpy sorts (the terminal is the unique
largest code, so ranks past the end never decide a comparison), and
occurrences by a scan of the text's bytes.

Each has a control beside it, the shortcut a faster program would be
tempted to take: suffixes ordered by their first ``depth`` symbols only
(an elastic loop stopped after one range), and patterns matched on their
first ``depth`` symbols only.  Both break the configuration's guarantee
that the index is the exact suffix order and that a lookup returns exactly
the positions where the whole pattern occurs.
"""

from __future__ import annotations

import numpy as np


def _heads(sorted_keys: np.ndarray) -> np.ndarray:
    """For each index of ``sorted_keys``, the first index holding its key."""
    new = np.empty(len(sorted_keys), bool)
    new[0] = True
    new[1:] = sorted_keys[1:] != sorted_keys[:-1]
    return np.maximum.accumulate(np.where(new, np.arange(len(new)), 0))


def _initial_keys(s: np.ndarray, base: int, depth: int):
    """Keys of the first ``depth`` symbols (codes + 1, 0 past the end),
    packed into int64 where ``depth`` symbols fit, else the first that
    do.  Returns (keys, symbols covered)."""
    bits = int(base).bit_length()
    k = min(depth, 62 // bits)
    n1 = len(s)
    key = np.zeros(n1 + k, np.int64)
    key[:n1] = s.astype(np.int64) + 1
    have = 1                      # key[i] packs symbols i .. i + have - 1
    while have < k:
        more = min(have, k - have)
        key[:n1 + k - have] = ((key[:n1 + k - have] << (bits * more))
                               | (key[have:] >> (bits * (have - more))))
        have += more
    return key[:n1], k


def suffix_array(s: np.ndarray, base: int,
                 depth: int | None = None) -> np.ndarray:
    """Suffix array of the terminated code string ``s``, by prefix
    doubling: a position's rank is where its group of equal prefixes
    starts in the sorted order, and each round re-sorts only the groups
    that still hold more than one position, by the rank ``k`` further on.

    ``depth`` is the control: order by the first ``depth`` symbols only,
    ties by position.  None orders the whole suffixes (the reference)."""
    n1 = len(s)
    limit = n1 if depth is None else depth
    key, k = _initial_keys(s, base, limit)
    order = np.argsort(key)
    rank = np.empty(n1, np.int64)
    rank[order] = _heads(key[order])
    todo = np.flatnonzero(np.bincount(rank, minlength=n1)[rank] > 1)
    while k < limit and len(todo):
        step = min(k, limit - k)
        ahead = todo + step
        nxt = np.where(ahead < n1, rank[np.minimum(ahead, n1 - 1)] + 1, 0)
        group = rank[todo]
        key = group * (n1 + 1) + nxt
        order = np.argsort(key)
        todo, group, key = todo[order], group[order], key[order]
        # a group keeps its start, and splits by ``nxt`` within it
        sub = _heads(key)
        rank[todo] = group + sub - _heads(group)
        alone = np.ones(len(key), bool)
        alone[1:] &= key[1:] != key[:-1]
        alone[:-1] &= key[:-1] != key[1:]
        todo = todo[~alone]
        k += step
    if not len(todo):
        sa = np.empty(n1, np.int64)
        sa[rank] = np.arange(n1)
        return sa
    return np.argsort(rank * n1 + np.arange(n1)).astype(np.int64)


def occurrences(text: bytes, pat, depth: int | None = None) -> list[int]:
    """Start positions of ``pat`` in ``text`` (overlaps too), in order.

    ``depth`` is the control: match the first ``depth`` symbols only."""
    if depth is not None:
        pat = pat[:depth]
    needle = bytes(int(c) for c in pat)
    out, i = [], text.find(needle)
    while i >= 0:
        out.append(i)
        i = text.find(needle, i + 1)
    return out

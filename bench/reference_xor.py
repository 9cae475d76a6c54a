"""The plain reference for XOR updates: a table of 64-bit words as numpy
computes it.

A 64-bit word is a row of two int32 lanes; XOR works lane by lane, so no
bit carries between them. The table starts at zeros and every update the
clients sent is XORed into its row. The table is held sparse, as the rows
that differ from zero, sorted by key: HPCC's table fills half of the
chips' memory, and a run's updates touch a small share of its rows.
Nothing here comes from the program under test.
"""

from __future__ import annotations

import numpy as np


def expected_rows(cols: int, parts):
    """``(keys, vals)``: the rows that are not zero after every ``(keys,
    vals)`` part of the sent stream (keys of any shape, values with
    ``cols`` lanes more), keys ascending; keys below 0 are padding."""
    parts = list(parts)
    k = np.concatenate([np.asarray(p[0]).reshape(-1) for p in parts])
    v = np.concatenate([np.asarray(p[1]).reshape(-1, cols) for p in parts])
    ok = k >= 0
    k, v = k[ok], v[ok]
    order = np.argsort(k, kind="stable")
    k, v = k[order], v[order]
    if not len(k):
        return k, v
    starts = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
    keys, vals = k[starts], np.bitwise_xor.reduceat(v, starts, axis=0)
    nz = vals.any(axis=1)
    return keys[nz], vals[nz]


def lookup(keys: np.ndarray, vals: np.ndarray, q) -> np.ndarray:
    """The rows of the sparse table ``(keys, vals)`` at keys ``q``: zeros
    where a key is not held."""
    q = np.asarray(q).reshape(-1)
    out = np.zeros((len(q), vals.shape[1]), vals.dtype)
    if len(keys):
        i = np.minimum(np.searchsorted(keys, q), len(keys) - 1)
        hit = keys[i] == q
        out[hit] = vals[i[hit]]
    return out

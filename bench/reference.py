"""The plain reference: a counter table as numpy computes it.

Every update the client sent is added into an int64 table with
``np.add.at``; the table then wraps to int32, as two's-complement int32
addition does. Nothing here comes from the program under test.
"""

from __future__ import annotations

import numpy as np


def wrap_int32(t: np.ndarray) -> np.ndarray:
    """int64 -> int32 modulo 2**32, the int32 sum's own wrap-around."""
    return ((t + 2**31) % 2**32 - 2**31).astype(np.int32)


def expected_table(n_keys: int, cols: int, parts) -> np.ndarray:
    """The int32 table after every ``(keys, vals, times)`` part of the
    sent stream (``generate.consumed``): ``times`` repeats of each part."""
    acc = np.zeros((n_keys, cols), np.int64)
    for keys, vals, times in parts:
        ok = keys >= 0
        np.add.at(acc, keys[ok], vals[ok].astype(np.int64) * times)
    return wrap_int32(acc)

"""The plain reference against JAX's own int32 scatter-add, wrap-around
included; and the control, which must come out wrong."""

import jax.numpy as jnp
import numpy as np

from bench import control, reference


def test_reference_equals_at_add_with_wraparound():
    rng = np.random.default_rng(0)
    n_keys, cols = 64, 4
    keys = rng.integers(0, n_keys, 5000).astype(np.int32)
    keys[::7] = -1                       # padding is ignored
    vals = rng.integers(-2**31, 2**31, (5000, cols),
                        dtype=np.int64).astype(np.int32)
    got = reference.expected_table(n_keys, cols, [(keys, vals, 1)])
    ok = keys >= 0
    want = jnp.zeros((n_keys, cols), jnp.int32).at[keys[ok]].add(vals[ok])
    assert np.array_equal(got, np.asarray(want))
    # the sums did wrap: int64 sums leave the int32 range
    wide = np.zeros((n_keys, cols), np.int64)
    np.add.at(wide, keys[ok], vals[ok].astype(np.int64))
    assert np.abs(wide).max() > 2**31


def test_repeated_parts_count_each_time():
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 32, 100).astype(np.int32)
    vals = rng.integers(-2**31, 2**31, (100, 2),
                        dtype=np.int64).astype(np.int32)
    three = reference.expected_table(32, 2, [(keys, vals, 3)])
    again = reference.expected_table(32, 2, [(keys, vals, 1)] * 3)
    assert np.array_equal(three, again)


def test_wrap_int32():
    x = np.array([2**31, -2**31 - 1, 2**32 + 5, -7], np.int64)
    assert reference.wrap_int32(x).tolist() == [-2**31, 2**31 - 1, 5, -7]


def test_the_control_comes_out_wrong():
    rng = np.random.default_rng(2)
    keys = rng.integers(0, 1 << 10, 1 << 14).astype(np.int32)
    vals = rng.integers(-2**31, 2**31, (1 << 14, 4),
                        dtype=np.int64).astype(np.int32)
    parts = [(keys, vals, 1)]
    want = reference.expected_table(1 << 10, 4, parts)
    got = control.control_table(1 << 10, 4, parts)
    assert (got != want).any(axis=1).sum() > 0.9 * (1 << 10)

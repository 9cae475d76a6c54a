"""CCache engine semantics: privatize/COps/merge, tree merge vs serial fold,
soft-merge coalescing. Collectives run under vmap(axis_name=...) so the
8-"core" tests work on one CPU device."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ccache
from repro.core import merge_functions as mf

N_CORES = 8


def run_cores(fn, *per_core_args):
    """Run fn per 'core' with a named axis (vmap stands in for the mesh)."""
    return jax.vmap(fn, axis_name="cores")(*per_core_args)


def test_cview_ops():
    v = ccache.privatize(jnp.asarray([1.0, 2.0]))
    assert jnp.array_equal(ccache.c_read(v), jnp.asarray([1.0, 2.0]))
    v = ccache.c_write(v, jnp.asarray([5.0, 6.0]))
    assert jnp.array_equal(v.src, jnp.asarray([1.0, 2.0]))  # source preserved
    v = ccache.c_update(v, lambda x: x + 1)
    assert jnp.array_equal(ccache.c_read(v), jnp.asarray([6.0, 7.0]))


@pytest.mark.parametrize("force_tree", [False, True])
def test_merge_equals_serial_fold_add(force_tree):
    mem = jnp.arange(4.0)
    upds = jnp.arange(N_CORES * 4, dtype=jnp.float32).reshape(N_CORES, 4)

    def core_fn(mem, upd):
        view = ccache.privatize(mem)
        view = ccache.c_write(view, view.upd + upd)
        return ccache.merge(view, mem, "cores", mf.ADD,
                            force_tree=force_tree)

    out = run_cores(core_fn, jnp.broadcast_to(mem, (N_CORES, 4)), upds)
    expected = mem + upds.sum(0)
    for c in range(N_CORES):  # every rank converges to the same memory copy
        np.testing.assert_allclose(np.asarray(out[c]), np.asarray(expected),
                                   rtol=1e-5)


@given(data=st.lists(st.floats(-10, 10, allow_nan=False, width=32),
                     min_size=N_CORES, max_size=N_CORES))
@settings(max_examples=20, deadline=None)
def test_tree_merge_max_any_order(data):
    vals = jnp.asarray(data, jnp.float32).reshape(N_CORES, 1)
    out = run_cores(
        lambda v: ccache.tree_merge(v, "cores", mf.MAX), vals)
    np.testing.assert_allclose(np.asarray(out),
                               np.full((N_CORES, 1), max(data)), rtol=1e-6)


def test_tree_merge_bitwise_or():
    vals = (jnp.uint32(1) << jnp.arange(N_CORES, dtype=jnp.uint32))[:, None]
    out = run_cores(lambda v: ccache.tree_merge(v, "cores", mf.BITWISE_OR),
                    vals)
    assert int(out[0, 0]) == (1 << N_CORES) - 1


def test_flexible_merge_saturating_observes_memory():
    """8 cores each add 2.0; saturation at 10 applies against memory=3."""
    mem = jnp.asarray([3.0])
    m = mf.saturating_add(10.0)

    def core_fn(mem):
        view = ccache.privatize(mem)
        view = ccache.c_write(view, view.upd + 2.0)
        return ccache.merge(view, mem, "cores", m, force_tree=True)

    out = run_cores(core_fn, jnp.broadcast_to(mem, (N_CORES, 1)))
    np.testing.assert_allclose(np.asarray(out[0]), [10.0])  # not 19


def test_soft_merge_coalesces_then_commits():
    mem = jnp.zeros((3,))

    def core_fn(mem, a, b):
        view = ccache.privatize(mem)
        view = ccache.c_write(view, view.upd + a)
        view, pending = ccache.soft_merge(view, None, mf.ADD)
        view = ccache.c_write(view, view.upd + b)
        view, pending = ccache.soft_merge(view, pending, mf.ADD)
        return ccache.commit(pending, mem, "cores", mf.ADD)

    a = jnp.ones((N_CORES, 3))
    b = 2 * jnp.ones((N_CORES, 3))
    out = run_cores(core_fn, jnp.broadcast_to(mem, (N_CORES, 3)), a, b)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.full(3, N_CORES * 3.0), rtol=1e-6)


def test_compressed_merge_close_to_exact():
    m = mf.int8_compressed_add()
    upds = jax.random.normal(jax.random.key(0), (N_CORES, 64))

    out = run_cores(
        lambda u: ccache.reduce_update(u, "cores", m, compress=True), upds)
    exact = np.asarray(upds.sum(0))
    scale = np.abs(exact).max()
    np.testing.assert_allclose(np.asarray(out[0]), exact,
                               atol=scale * 0.12)


def test_int8_wire_is_smaller():
    m = mf.int8_compressed_add()
    enc = m.encode(jnp.ones((1024,), jnp.float32))
    assert enc["q"].dtype == jnp.int8
    assert enc["q"].size == 1024  # 4x fewer bytes than f32


def test_non_power_of_two_axis_fallback():
    vals = jnp.arange(6, dtype=jnp.float32).reshape(6, 1)
    out = jax.vmap(lambda v: ccache.tree_merge(v, "cores", mf.ADD),
                   axis_name="cores")(vals)
    np.testing.assert_allclose(np.asarray(out[0]), [15.0], rtol=1e-6)

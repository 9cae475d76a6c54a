"""Hierarchical (topology-aware) merge ≡ flat tree_merge, for every merge
family, on power-of-two and non-power-of-two group shapes.

Collectives run under ``vmap(axis_name=...)`` (the single-device stand-in for
the mesh); that also exercises the software intra-group path, since vmap
rejects ``axis_index_groups`` — the fused-collective fast path is covered by
the shard_map lowering test at the bottom and the hierarchy benchmark.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ccache
from repro.core import merge_functions as mf
from repro.core.grad_merge import merge_gradients

# (axis size, group size): pow2/pow2, pow2 groups in a non-pow2 count of
# groups (12/4 -> 3 groups, ring inter), non-pow2 groups (6/3, 12/6), and
# the degenerate single-group / all-groups edges.
SHAPES = [(8, 2), (8, 4), (8, 8), (6, 3), (12, 4), (12, 6), (8, 1)]


def run_cores(fn, *per_core_args):
    return jax.vmap(fn, axis_name="cores")(*per_core_args)


def _hier(v, topo, merge, **kw):
    return ccache.hierarchical_merge(v, "cores", merge, topo, **kw)


def _flat_fold(vals, merge):
    acc = vals[0]
    for i in range(1, vals.shape[0]):
        acc = merge.combine(acc, vals[i])
    return np.asarray(acc)


@pytest.mark.parametrize("size,group", SHAPES)
def test_hier_add_equals_flat(size, group):
    topo = ccache.MergeTopology(group_size=group)
    vals = jax.random.normal(jax.random.key(size * 31 + group), (size, 5))
    out = run_cores(lambda v: _hier(v, topo, mf.ADD), vals)
    exact = np.asarray(vals.sum(0))
    for c in range(size):  # every rank ends with the full combination
        np.testing.assert_allclose(np.asarray(out[c]), exact,
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("size,group", SHAPES)
def test_hier_max_equals_flat_bitwise_exact(size, group):
    topo = ccache.MergeTopology(group_size=group)
    vals = jax.random.normal(jax.random.key(7), (size, 4))
    out = run_cores(lambda v: _hier(v, topo, mf.MAX), vals)
    np.testing.assert_array_equal(
        np.asarray(out), np.broadcast_to(np.asarray(vals.max(0)), (size, 4)))


@pytest.mark.parametrize("size,group", SHAPES)
def test_hier_bitwise_or_all_bits(size, group):
    topo = ccache.MergeTopology(group_size=group)
    vals = (jnp.uint32(1) << jnp.arange(size, dtype=jnp.uint32))[:, None]
    out = run_cores(lambda v: _hier(v, topo, mf.BITWISE_OR), vals)
    assert np.all(np.asarray(out) == (1 << size) - 1)


@pytest.mark.parametrize("size,group", SHAPES)
def test_hier_software_combine_complex_mul(size, group):
    """A combine COUP cannot express (no xla_reduce): complex product."""
    topo = ccache.MergeTopology(group_size=group)
    vals = (jax.random.normal(jax.random.key(3), (size, 3, 2)) * 0.3
            + jnp.asarray([1.0, 0.0]))
    out = run_cores(lambda v: _hier(v, topo, mf.COMPLEX_MUL), vals)
    flat = run_cores(
        lambda v: ccache.tree_merge(v, "cores", mf.COMPLEX_MUL), vals)
    np.testing.assert_allclose(np.asarray(out), np.asarray(flat),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[0]),
                               _flat_fold(vals, mf.COMPLEX_MUL),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("size,group", [(8, 4), (8, 2), (12, 4), (6, 3)])
def test_hier_compressed_int8_within_tolerance(size, group):
    m = mf.int8_compressed_add()
    topo = ccache.MergeTopology(group_size=group)
    upds = jax.random.normal(jax.random.key(0), (size, 64))
    out = run_cores(lambda u: _hier(u, topo, m, compress=True), upds)
    exact = np.asarray(upds.sum(0))
    scale = np.abs(exact).max()
    for c in range(size):
        np.testing.assert_allclose(np.asarray(out[c]), exact,
                                   atol=scale * 0.2 + 1e-3)


@pytest.mark.parametrize("size,group", [(8, 4), (6, 3)])
def test_reduce_update_topology_routes_hierarchical(size, group):
    topo = ccache.MergeTopology(group_size=group)
    vals = jax.random.normal(jax.random.key(1), (size, 4))
    hier = run_cores(
        lambda v: ccache.reduce_update(v, "cores", mf.ADD, topology=topo),
        vals)
    flat = run_cores(
        lambda v: ccache.reduce_update(v, "cores", mf.ADD, force_tree=True),
        vals)
    np.testing.assert_allclose(np.asarray(hier), np.asarray(flat),
                               rtol=1e-5, atol=1e-5)


def test_full_merge_with_topology_saturating():
    """End-to-end CCache merge: the memory-observed saturation threshold
    must behave identically through the hierarchical path."""
    mem = jnp.asarray([3.0])
    m = mf.saturating_add(10.0)
    topo = ccache.MergeTopology(group_size=4)

    def core_fn(mem):
        view = ccache.privatize(mem)
        view = ccache.c_write(view, view.upd + 2.0)
        return ccache.merge(view, mem, "cores", m, force_tree=True,
                            topology=topo)

    out = run_cores(core_fn, jnp.broadcast_to(mem, (8, 1)))
    np.testing.assert_allclose(np.asarray(out[0]), [10.0])  # not 19


def test_commit_with_topology():
    mem = jnp.zeros((3,))
    topo = ccache.MergeTopology(group_size=2)

    def core_fn(mem, a):
        view = ccache.privatize(mem)
        view = ccache.c_write(view, view.upd + a)
        view, pending = ccache.soft_merge(view, None, mf.ADD)
        return ccache.commit(pending, mem, "cores", mf.ADD, topology=topo)

    a = jnp.arange(8 * 3, dtype=jnp.float32).reshape(8, 3)
    out = run_cores(core_fn, jnp.broadcast_to(mem, (8, 3)), a)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(a.sum(0)),
                               rtol=1e-6)


def test_merge_gradients_topology_matches_flat():
    grads = {"w": jax.random.normal(jax.random.key(5), (8, 6)),
             "b": jax.random.normal(jax.random.key(6), (8, 2))}
    topo = ccache.MergeTopology(group_size=4)
    hier = jax.vmap(
        lambda g: merge_gradients(g, "cores", topology=topo),
        axis_name="cores")(grads)
    flat = jax.vmap(
        lambda g: merge_gradients(g, "cores"), axis_name="cores")(grads)
    for k in grads:
        np.testing.assert_allclose(np.asarray(hier[k]), np.asarray(flat[k]),
                                   rtol=1e-5, atol=1e-6)


def test_topology_validation():
    topo = ccache.MergeTopology(group_size=3)
    vals = jnp.zeros((8, 2))
    with pytest.raises(ValueError, match="not divisible"):
        run_cores(lambda v: _hier(v, topo, mf.ADD), vals)
    with pytest.raises(ValueError, match="group_size"):
        ccache.MergeTopology(group_size=0).validate(8)


def test_compat_axis_size_under_vmap():
    """The engine sizes its merge axis with ``lax.axis_size``, which must be
    static under the vmap executor the fast tests use."""
    out = jax.vmap(lambda x: x * 0 + jax.lax.axis_size("i"),
                   axis_name="i")(jnp.zeros(6))
    np.testing.assert_array_equal(np.asarray(out), np.full(6, 6.0))


def test_hier_lowers_on_shard_map_mesh():
    """The shard_map lowering path (where the fused intra-group collective
    applies) at least compiles and runs on whatever devices exist."""
    from jax.sharding import PartitionSpec as P

    n_dev = jax.device_count()
    mesh = jax.make_mesh((n_dev,), ("dp",))
    topo = ccache.MergeTopology(group_size=n_dev)
    f = jax.jit(jax.shard_map(
        lambda u: ccache.hierarchical_merge(u, "dp", mf.ADD, topo),
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False))
    x = jnp.arange(n_dev * 4, dtype=jnp.float32).reshape(n_dev, 4)
    out = f(x)
    np.testing.assert_allclose(
        np.asarray(out),
        np.broadcast_to(np.asarray(x).sum(0), (n_dev, 4)), rtol=1e-6)

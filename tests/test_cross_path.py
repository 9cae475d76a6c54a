"""Cross-path differential suite: every merge execution path must agree.

The engine now has four ways to produce "the combination of all ranks'
updates": the flat recursive-doubling ``tree_merge``, the compiled-plan
``hierarchical_merge``, the scheduled ``defer_cascade`` (merge-on-evict),
and the overlapped ``overlap_cascade`` launch/land pipeline. They reorder
the same commutative combine across different link classes and steps, so
any divergence is an engine bug, not a modeling choice.

This suite drives randomized N-level topologies x merge functions x
execution flags through all four paths and asserts they agree:

* exact (bitwise-equal) for ADD/MAX/MIN — updates are integer-valued
  floats, so reassociation cannot round differently — and for the
  BITWISE_OR lattice join on int32 bitmaps (the paper's BFS merge);
* tolerance-bounded for COMPLEX_MUL (multiplication reordering) and the
  int8-compressed wire format (per-round quantization).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ccache
from repro.core import merge_functions as mf
from repro.core.merge_plan import MergePlan


def _plan_spec(sizes, n_defer):
    parts = []
    for i, s in enumerate(sizes):
        flags = ":defer" if i >= len(sizes) - n_defer else ""
        parts.append(f"l{i}:{s}{flags}")
    return ",".join(parts)


def _updates(merge_name, seed, size):
    key = jax.random.key(seed)
    if merge_name == "complex_mul":
        # Near-identity complex factors keep products well-conditioned.
        base = jax.random.normal(key, (size, 3, 2)) * 0.1
        return {"a": base + jnp.asarray([1.0, 0.0]),
                "b": base[:, :2] * 0.5 + jnp.asarray([1.0, 0.0])}
    if merge_name == "or":
        # int32 bitmaps: the lattice join is exact by construction.
        bits = jax.random.randint(key, (size, 2, 5), 0, 1 << 15)
        return {"a": bits.astype(jnp.int32),
                "b": (bits[:, 0, :3] << 3).astype(jnp.int32)}
    # Integer-valued floats: ADD/MAX/MIN reassociate exactly.
    ints = jax.random.randint(key, (size, 2, 5), -8, 9)
    return {"a": ints.astype(jnp.float32),
            "b": ints[:, 0, :3].astype(jnp.float32) * 2.0}


def _merge_and_tols(merge_name, compressed):
    if merge_name == "complex_mul":
        return mf.COMPLEX_MUL, dict(rtol=1e-4, atol=1e-5)
    if merge_name == "max":
        return mf.MAX, dict(rtol=0, atol=0)
    if merge_name == "min":
        return mf.MIN, dict(rtol=0, atol=0)
    if merge_name == "or":
        return mf.BITWISE_OR, dict(rtol=0, atol=0)
    if compressed:
        # int8 wire quantization: each round rounds to ~amax/254.
        return mf.int8_compressed_add(), dict(rtol=0.05, atol=6.0)
    return mf.ADD, dict(rtol=0, atol=0)


def _assert_trees_close(got, want, tols, what):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if tols["rtol"] == 0 and tols["atol"] == 0:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=what)
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       err_msg=what, **tols)


TOPOLOGIES = [
    (2, 2), (2, 4), (4, 2), (2, 3), (3, 2), (4, 4),
    (2, 2, 2), (2, 2, 3), (2, 3, 2), (4, 2, 2), (2, 2, 4),
]


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6),
       sizes=st.sampled_from(TOPOLOGIES),
       merge_name=st.sampled_from(["add", "max", "min", "or",
                                   "complex_mul"]),
       lane=st.booleans(),
       compressed=st.booleans(),
       n_defer=st.integers(min_value=0, max_value=2))
def test_property_all_merge_paths_agree(seed, sizes, merge_name, lane,
                                        compressed, n_defer):
    n_defer = min(n_defer, len(sizes) - 1)  # defer is a strict suffix
    # Compression needs a wire codec; only the additive merge has one here.
    compressed = compressed and merge_name == "add"
    merge, tols = _merge_and_tols(merge_name, compressed)
    size = 1
    for s in sizes:
        size *= s
    plan = MergePlan.parse(_plan_spec(sizes, n_defer), lane_parallel=lane)
    upds = _updates(merge_name, seed, size)

    # Path 1: flat recursive-doubling butterfly (the reference). The
    # uncompressed flat merge is the exact combination; compressed paths
    # are compared against it within the codec's tolerance.
    flat = jax.vmap(lambda u: ccache.tree_merge(u, "cores", merge),
                    axis_name="cores")(upds)

    # Path 2: compiled-plan hierarchical merge (all levels eager).
    hier = jax.vmap(
        lambda u: ccache.hierarchical_merge(u, "cores", merge, plan,
                                            compress=compressed),
        axis_name="cores")(upds)
    _assert_trees_close(hier, flat, tols, "hierarchical_merge vs tree_merge")

    n_def = len(ccache.deferred_stages_of(plan, size))
    if n_def == 0:
        return

    # Path 3: the scheduled cascade, single full-commit cycle (due = all).
    like = jax.tree.map(lambda x: x[0], upds)
    pends = tuple(
        jax.vmap(lambda _: merge.tree_identity(like))(jnp.zeros(size))
        for _ in range(n_def))

    def cascade_step(u, *p):
        new_p, settled = ccache.defer_cascade(
            u, list(p), n_def, "cores", merge, plan, compress=compressed)
        return tuple(new_p), settled

    _, settled = jax.vmap(cascade_step, axis_name="cores")(upds, *pends)
    _assert_trees_close(settled, flat, tols,
                        "defer_cascade settled vs tree_merge")

    # Path 4: overlapped launch/land — launch on the full-commit step,
    # land (top-level exchange) afterwards via settle_inflight.
    inflight = jax.vmap(lambda _: merge.tree_identity(like))(jnp.zeros(size))

    def launch_step(u, inf, *p):
        new_p, new_inf, landed = ccache.overlap_cascade(
            u, list(p), inf, n_def, False, "cores", merge, plan,
            compress=compressed)
        assert landed is None
        return tuple(new_p), new_inf

    _, launched = jax.vmap(launch_step, axis_name="cores")(upds, inflight,
                                                           *pends)
    landed = jax.vmap(
        lambda x: ccache.settle_inflight(x, "cores", merge, plan,
                                         compress=compressed),
        axis_name="cores")(launched)
    _assert_trees_close(landed, flat, tols,
                        "overlap launch/land vs tree_merge")

    # The land half via overlap_cascade's land flag must agree with the
    # standalone settle (same program shape the train step compiles).
    # The next step contributes a zero delta so only the landing is seen.
    zero_delta = jax.tree.map(lambda x: merge.identity(x.shape, x.dtype),
                              upds)

    def land_step(u, inf, *p):
        new_p, new_inf, landed2 = ccache.overlap_cascade(
            u, list(p), inf, 0, True, "cores", merge, plan,
            compress=compressed)
        return landed2

    landed2 = jax.vmap(land_step, axis_name="cores")(zero_delta, launched,
                                                     *pends)
    _assert_trees_close(landed2, flat, tols,
                        "overlap_cascade land vs tree_merge")


def test_cross_path_two_cycle_add_exact():
    """Two full cycles through cascade and overlap paths both equal two
    eager cycle sums, bitwise, on integer-valued floats."""
    size = 8
    plan = MergePlan.parse("l0:2,l1:2,l2:2:defer", lane_parallel=True)
    K = 2
    T = 2 * K
    upds = jax.random.randint(jax.random.key(3), (T, size, 4),
                              -8, 9).astype(jnp.float32)

    def eager_cycle(lo, hi):
        acc = None
        for t in range(lo, hi):
            m = jax.vmap(lambda v: ccache.tree_merge(v, "cores", mf.ADD),
                         axis_name="cores")(upds[t])
            acc = m if acc is None else acc + m
        return acc

    # cascade path
    pends = (jnp.zeros((size, 4)),)
    cascade_commits = []
    for t in range(1, T + 1):
        due = 1 if t % K == 0 else 0

        def step(g, p):
            new_p, settled = ccache.defer_cascade(g, [p], due, "cores",
                                                  mf.ADD, plan)
            return new_p[0], settled

        pends0, settled = jax.vmap(step, axis_name="cores")(upds[t - 1],
                                                            pends[0])
        pends = (pends0,)
        if due:
            cascade_commits.append(settled)

    # overlap path: launch at t=K, 2K; land at t=K+1 and via final settle
    pend = jnp.zeros((size, 4))
    inflight = jnp.zeros((size, 4))
    overlap_commits = []
    for t in range(1, T + 1):
        due = 1 if t % K == 0 else 0
        land = t > 1 and (t - 1) % K == 0

        def step(g, inf, p):
            new_p, new_inf, landed = ccache.overlap_cascade(
                g, [p], inf, due, land, "cores", mf.ADD, plan)
            return new_p[0], new_inf, landed

        pend, inflight, landed = jax.vmap(step, axis_name="cores")(
            upds[t - 1], inflight, pend)
        if land:
            overlap_commits.append(landed)
    # the final launched cycle lands after the loop (the flush)
    overlap_commits.append(jax.vmap(
        lambda x: ccache.settle_inflight(x, "cores", mf.ADD, plan),
        axis_name="cores")(inflight))

    for c_idx, (lo, hi) in enumerate([(0, K), (K, T)]):
        want = np.asarray(eager_cycle(lo, hi))
        np.testing.assert_array_equal(np.asarray(cascade_commits[c_idx]),
                                      want, err_msg=f"cascade cycle {c_idx}")
        np.testing.assert_array_equal(np.asarray(overlap_commits[c_idx]),
                                      want, err_msg=f"overlap cycle {c_idx}")


def test_overlap_cascade_validates_inputs():
    plan = MergePlan.parse("l0:2,l1:2:defer")
    z = jnp.zeros((4, 3))
    with pytest.raises(ValueError, match="pendings"):
        jax.vmap(lambda g: ccache.overlap_cascade(
            g, [g, g], g, 0, False, "cores", mf.ADD, plan),
            axis_name="cores")(z)
    with pytest.raises(ValueError, match="due"):
        jax.vmap(lambda g: ccache.overlap_cascade(
            g, [g], g, 2, False, "cores", mf.ADD, plan),
            axis_name="cores")(z)
    with pytest.raises(ValueError, match="no deferred"):
        jax.vmap(lambda g: ccache.overlap_cascade(
            g, [], g, 0, False, "cores", mf.ADD,
            MergePlan.parse("l0:2,l1:2")),
            axis_name="cores")(z)
    with pytest.raises(ValueError, match="no deferred"):
        jax.vmap(lambda g: ccache.settle_inflight(
            g, "cores", mf.ADD, MergePlan.parse("l0:2,l1:2")),
            axis_name="cores")(z)

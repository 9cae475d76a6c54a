"""MergePlan IR: N-level hierarchical merge ≡ flat tree_merge, lane-parallel
exchange, merge-on-evict (deferred levels), and the train-path threading.

Collectives run under ``vmap(axis_name=...)`` (the single-device stand-in
for the mesh); the shard_map lowering paths are covered by the subprocess
train test at the bottom and the hierarchy benchmark.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ccache
from repro.core import merge_functions as mf
from repro.core.merge_plan import (MergeLevel, MergePlan, compile_plan,
                                   split_eager_deferred)

ENV = dict(os.environ, PYTHONPATH=os.pathsep.join(
    [os.path.abspath("src"), os.environ.get("PYTHONPATH", "")]))

# (axis size, spec): 3-level pow2, non-pow2 middle level, wider chip level,
# 4 levels, and a size-1 level that must compile away.
PLANS = [
    (8, "chip:2,host:2,pod:2"),
    (12, "chip:2,host:3,pod:2"),
    (16, "chip:4,host:2,pod:2"),
    (16, "a:2,b:2,c:2,d:2"),
    (8, "chip:2,host:1,pod:4"),
]


def run_cores(fn, *per_core_args):
    return jax.vmap(fn, axis_name="cores")(*per_core_args)


def _hier(v, plan, merge, **kw):
    return ccache.hierarchical_merge(v, "cores", merge, plan, **kw)


# ---------------------------------------------------------------------------
# IR construction / validation
# ---------------------------------------------------------------------------


def test_parse_roundtrip():
    plan = MergePlan.parse("chip:4,host:16,pod:2:defer:compress",
                           lane_parallel=True)
    assert plan.level_names() == ("chip", "host", "pod")
    assert plan.level_sizes() == (4, 16, 2)
    assert plan.num_ranks == 128
    assert plan.strides() == [1, 4, 64]
    assert plan.levels[2].defer and plan.levels[2].compress
    assert not plan.levels[0].defer
    assert plan.lane_parallel


def test_parse_flags_and_errors():
    plan = MergePlan.parse("intra:8:software:ici,inter:2:dci")
    assert plan.levels[0].combine_mode == "software"
    assert plan.levels[1].transport == "dci"
    for bad in ("chip", "chip:x", "chip:4:bogus", ""):
        with pytest.raises(ValueError):
            MergePlan.parse(bad)


def test_axis_size_mismatch_is_a_clear_error():
    """A plan whose level-size product mismatches the axis raises instead of
    silently producing wrong groups."""
    plan = MergePlan.parse("chip:2,pod:2")
    vals = jnp.zeros((6, 3))
    with pytest.raises(ValueError, match="product of level sizes"):
        run_cores(lambda v: _hier(v, plan, mf.ADD), vals)
    with pytest.raises(ValueError, match="6 ranks.*covers 4|covers 4"):
        plan.validate(6)


def test_topology_group_size_mismatch_still_raises():
    topo = ccache.MergeTopology(group_size=5)
    with pytest.raises(ValueError, match="not divisible"):
        run_cores(lambda v: _hier(v, topo, mf.ADD), jnp.zeros((8, 2)))


def test_defer_must_be_suffix():
    with pytest.raises(ValueError, match="suffix"):
        MergePlan(levels=(MergeLevel("a", 2, defer=True),
                          MergeLevel("b", 2)))
    # deferring the top two levels is fine
    MergePlan(levels=(MergeLevel("a", 2), MergeLevel("b", 2, defer=True),
                      MergeLevel("c", 2, defer=True)))


def test_duplicate_level_names_rejected():
    with pytest.raises(ValueError, match="duplicate"):
        MergePlan.parse("pod:2,pod:2")


def test_compile_plan_drops_unit_levels_and_resolves_modes():
    plan = MergePlan.parse("chip:4,host:1,pod:2", lane_parallel=True)
    stages = compile_plan(plan, 8)
    assert [s.name for s in stages] == ["chip", "pod"]
    assert stages[0].combine_mode == "xla"       # innermost auto -> fused
    assert not stages[0].lane_parallel           # stride 1: no lanes to shard
    assert stages[1].combine_mode == "software"  # upper levels are software
    assert stages[1].lane_parallel
    assert stages[1].stride == 4 and stages[1].block == 8


def test_split_eager_deferred():
    plan = MergePlan.parse("chip:2,host:2,pod:2:defer")
    eager, deferred = split_eager_deferred(compile_plan(plan, 8))
    assert [s.name for s in eager] == ["chip", "host"]
    assert [s.name for s in deferred] == ["pod"]


# ---------------------------------------------------------------------------
# N-level merge ≡ flat, both execution strategies
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("size,spec", PLANS)
@pytest.mark.parametrize("lane", [False, True])
def test_nlevel_add_equals_flat(size, spec, lane):
    plan = MergePlan.parse(spec, lane_parallel=lane)
    vals = jax.random.normal(jax.random.key(size), (size, 5))
    out = run_cores(lambda v: _hier(v, plan, mf.ADD), vals)
    exact = np.asarray(vals.sum(0))
    for c in range(size):  # every rank ends with the full combination
        np.testing.assert_allclose(np.asarray(out[c]), exact,
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("size,spec", PLANS)
@pytest.mark.parametrize("lane", [False, True])
def test_nlevel_lattice_merges_bitwise_equal_flat(size, spec, lane):
    """MAX and OR are order-insensitive: the N-level result must be
    bitwise-identical to the flat tree_merge on every rank."""
    plan = MergePlan.parse(spec, lane_parallel=lane)
    vals = jax.random.normal(jax.random.key(7), (size, 4))
    out = run_cores(lambda v: _hier(v, plan, mf.MAX), vals)
    flat = run_cores(lambda v: ccache.tree_merge(v, "cores", mf.MAX), vals)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(flat))

    bits = (jnp.uint32(1) << jnp.arange(size, dtype=jnp.uint32))[:, None]
    outb = run_cores(lambda v: _hier(v, plan, mf.BITWISE_OR), bits)
    assert np.all(np.asarray(outb) == (1 << size) - 1)


@pytest.mark.parametrize("size,spec", PLANS)
@pytest.mark.parametrize("lane", [False, True])
def test_nlevel_software_combine_complex_mul(size, spec, lane):
    """A combine COUP cannot express (no xla_reduce), with a structured
    wire atom (real/imag pairs) exercising atom-aligned lane chunking."""
    plan = MergePlan.parse(spec, lane_parallel=lane)
    vals = (jax.random.normal(jax.random.key(3), (size, 3, 2)) * 0.3
            + jnp.asarray([1.0, 0.0]))
    out = run_cores(lambda v: _hier(v, plan, mf.COMPLEX_MUL), vals)
    flat = run_cores(
        lambda v: ccache.tree_merge(v, "cores", mf.COMPLEX_MUL), vals)
    np.testing.assert_allclose(np.asarray(out), np.asarray(flat),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("lane", [False, True])
def test_nlevel_compress_outermost_within_tolerance(lane):
    m = mf.int8_compressed_add()
    plan = MergePlan.parse("chip:2,host:2,pod:2", lane_parallel=lane)
    upds = jax.random.normal(jax.random.key(0), (8, 64))
    out = run_cores(lambda u: _hier(u, plan, m, compress=True), upds)
    exact = np.asarray(upds.sum(0))
    scale = np.abs(exact).max()
    for c in range(8):
        np.testing.assert_allclose(np.asarray(out[c]), exact,
                                   atol=scale * 0.2 + 1e-3)


def test_compress_survives_unit_outermost_level():
    """compress=True must land on the outermost *executing* level; a size-1
    outermost level (e.g. group_size == axis size) used to swallow it."""
    m = mf.int8_compressed_add()
    upds = jax.random.normal(jax.random.key(9), (8, 64)) + 0.5
    exact = np.asarray(upds.sum(0))
    for topo in (ccache.MergeTopology(group_size=8),
                 MergePlan.parse("chip:2,host:4,pod:1")):
        out = run_cores(lambda u: _hier(u, topo, m, compress=True), upds)
        err = np.abs(np.asarray(out[0]) - exact).max()
        assert err > 1e-4, (topo, err)  # quantization noise proves the codec ran
        np.testing.assert_allclose(np.asarray(out[0]), exact,
                                   atol=np.abs(exact).max() * 0.2 + 1e-3)


def test_per_level_compress_flag():
    m = mf.int8_compressed_add()
    plan = MergePlan.parse("chip:2,host:2,pod:2:compress")
    upds = jax.random.normal(jax.random.key(1), (8, 32))
    out = run_cores(lambda u: _hier(u, plan, m), upds)
    exact = np.asarray(upds.sum(0))
    scale = np.abs(exact).max()
    np.testing.assert_allclose(np.asarray(out[0]), exact,
                               atol=scale * 0.2 + 1e-3)


def test_compress_without_codec_is_a_loud_error():
    """compress=True with a merge that defines no encode/decode used to
    silently exchange full-width bytes; every path must raise instead."""
    upds = jnp.ones((8, 8))
    # flat tree_merge / reduce_update
    with pytest.raises(ValueError, match="encode/decode"):
        run_cores(lambda u: ccache.tree_merge(u, "cores", mf.ADD,
                                              compress=True), upds)
    with pytest.raises(ValueError, match="encode/decode"):
        run_cores(lambda u: ccache.reduce_update(u, "cores", mf.ADD,
                                                 compress=True), upds)
    # hierarchical: function-level compress lands on the outermost level
    plan = MergePlan.parse("chip:2,host:2,pod:2")
    with pytest.raises(ValueError, match="encode/decode"):
        run_cores(lambda u: _hier(u, plan, mf.ADD, compress=True), upds)
    # per-level compress flags validated in compile_plan
    flagged = MergePlan.parse("chip:2,host:2,pod:2:compress")
    with pytest.raises(ValueError, match="encode/decode"):
        compile_plan(flagged, 8, merge_fn=mf.MAX)
    with pytest.raises(ValueError, match="encode/decode"):
        run_cores(lambda u: _hier(u, flagged, mf.ADD), upds)
    # a size-1 compress level has no wire: not an error
    compile_plan(MergePlan.parse("chip:8,host:1:compress"), 8,
                 merge_fn=mf.ADD)
    # with a codec everything still flows
    compile_plan(flagged, 8, merge_fn=mf.int8_compressed_add())


def test_payload_smaller_than_lane_count():
    """Lane chunking pads: a 2-element payload over 4-lane units."""
    plan = MergePlan.parse("chip:4,pod:2", lane_parallel=True)
    vals = jax.random.normal(jax.random.key(2), (8, 2))
    out = run_cores(lambda v: _hier(v, plan, mf.ADD), vals)
    for c in range(8):
        np.testing.assert_allclose(np.asarray(out[c]),
                                   np.asarray(vals.sum(0)),
                                   rtol=1e-5, atol=1e-5)


def test_topology_to_plan_matches_topology_engine():
    """The two-level MergeTopology shorthand and its compiled MergePlan
    produce identical results (same stages underneath)."""
    topo = ccache.MergeTopology(group_size=4)
    plan = topo.to_plan(8)
    vals = jax.random.normal(jax.random.key(4), (8, 6))
    a = run_cores(lambda v: _hier(v, topo, mf.MAX), vals)
    b = run_cores(lambda v: _hier(v, plan, mf.MAX), vals)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lane_parallel_topology_shorthand():
    topo = ccache.MergeTopology(group_size=4, lane_parallel=True)
    vals = jax.random.normal(jax.random.key(5), (8, 16))
    out = run_cores(lambda v: _hier(v, topo, mf.ADD), vals)
    for c in range(8):
        np.testing.assert_allclose(np.asarray(out[c]),
                                   np.asarray(vals.sum(0)),
                                   rtol=1e-5, atol=1e-5)


def test_reduce_update_and_merge_route_plans():
    plan = MergePlan.parse("chip:2,host:2,pod:2")
    vals = jax.random.normal(jax.random.key(6), (8, 4))
    hier = run_cores(
        lambda v: ccache.reduce_update(v, "cores", mf.ADD, topology=plan),
        vals)
    flat = run_cores(
        lambda v: ccache.reduce_update(v, "cores", mf.ADD, force_tree=True),
        vals)
    np.testing.assert_allclose(np.asarray(hier), np.asarray(flat),
                               rtol=1e-5, atol=1e-5)

    mem = jnp.asarray([3.0])
    m = mf.saturating_add(10.0)

    def core_fn(mem):
        view = ccache.privatize(mem)
        view = ccache.c_write(view, view.upd + 2.0)
        return ccache.merge(view, mem, "cores", m, topology=plan)

    out = run_cores(core_fn, jnp.broadcast_to(mem, (8, 1)))
    np.testing.assert_allclose(np.asarray(out[0]), [10.0])  # not 19


# ---------------------------------------------------------------------------
# Merge-on-evict: K deferred commits ≡ K eager merges (property-style)
# ---------------------------------------------------------------------------


def _steps_for(merge, size, steps, seed):
    if merge is mf.COMPLEX_MUL:
        return (jax.random.normal(jax.random.key(seed),
                                  (steps, size, 3, 2)) * 0.2
                + jnp.asarray([1.0, 0.0]))
    return jax.random.normal(jax.random.key(seed), (steps, size, 3))


def _mem_for(merge):
    if merge is mf.COMPLEX_MUL:
        return jnp.zeros((3, 2)).at[..., 1].set(0.5).at[..., 0].set(1.0)
    return jnp.full((3,), 0.25)


def _run_defer_vs_eager(merge, size, spec, k, lane, seed):
    eager_plan = MergePlan.parse(spec, lane_parallel=lane)
    defer_spec = spec.rsplit(",", 1)
    defer_plan = MergePlan.parse(
        ",".join(defer_spec[:-1] + [defer_spec[-1] + ":defer"]),
        lane_parallel=lane)
    upds = _steps_for(merge, size, k, seed)
    mem0 = _mem_for(merge)

    def eager(mem):
        for t in range(k):
            view = ccache.privatize(mem)
            view = ccache.c_update(
                view, lambda u, t=t: merge.combine(
                    u, upds[t][jax.lax.axis_index("cores")]))
            mem = ccache.merge(view, mem, "cores", merge,
                               topology=eager_plan)
        return mem

    def deferred(mem):
        pending = None
        view = ccache.privatize(mem)
        for t in range(k):
            view = ccache.c_update(
                view, lambda u, t=t: merge.combine(
                    u, upds[t][jax.lax.axis_index("cores")]))
            view, pending = ccache.soft_merge(view, pending, merge,
                                              axis_name="cores",
                                              plan=defer_plan)
        return ccache.commit_deferred(pending, mem, "cores", merge,
                                      defer_plan)

    memb = jnp.broadcast_to(mem0, (size,) + mem0.shape)
    return run_cores(eager, memb), run_cores(deferred, memb)


@settings(max_examples=8, deadline=None)
@given(k=st.integers(min_value=1, max_value=5),
       lane=st.booleans(),
       seed=st.integers(min_value=0, max_value=10**6),
       shape=st.sampled_from([(8, "chip:2,host:2,pod:2"),
                              (12, "chip:2,host:3,pod:2")]))
def test_property_defer_add_equals_eager(k, lane, seed, shape):
    size, spec = shape
    a, b = _run_defer_vs_eager(mf.ADD, size, spec, k, lane, seed)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(k=st.integers(min_value=1, max_value=5),
       lane=st.booleans(),
       seed=st.integers(min_value=0, max_value=10**6),
       shape=st.sampled_from([(8, "chip:2,host:2,pod:2"),
                              (12, "chip:2,host:3,pod:2")]))
def test_property_defer_max_bitwise_equals_eager(k, lane, seed, shape):
    size, spec = shape
    a, b = _run_defer_vs_eager(mf.MAX, size, spec, k, lane, seed)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=6, deadline=None)
@given(k=st.integers(min_value=1, max_value=4),
       lane=st.booleans(),
       seed=st.integers(min_value=0, max_value=10**6))
def test_property_defer_custom_software_combine(k, lane, seed):
    """The paper's headline flexibility: a software combine (complex
    product) survives K-step deferral unchanged."""
    a, b = _run_defer_vs_eager(mf.COMPLEX_MUL, 8, "chip:2,host:2,pod:2",
                               k, lane, seed)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)


def test_soft_merge_without_plan_unchanged():
    """Legacy soft_merge (no plan) still coalesces locally with zero
    collectives and commits through the full reduction."""
    mem = jnp.zeros((3,))
    plan = MergePlan.parse("chip:2,host:2,pod:2")

    def core_fn(mem, a):
        view = ccache.privatize(mem)
        view = ccache.c_write(view, view.upd + a)
        view, pending = ccache.soft_merge(view, None, mf.ADD)
        return ccache.commit(pending, mem, "cores", mf.ADD, topology=plan)

    a = jnp.arange(8 * 3, dtype=jnp.float32).reshape(8, 3)
    out = run_cores(core_fn, jnp.broadcast_to(mem, (8, 3)), a)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(a.sum(0)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# Per-level wire classification (hlo_cost)
# ---------------------------------------------------------------------------

_LEVEL_HLO = """
HloModule t, num_partitions=8
ENTRY %main (p0: f32[16]) -> f32[16] {
  %p0 = f32[16]{0} parameter(0)
  ROOT %cp = f32[16]{0} collective-permute(%p0), \
source_target_pairs={{0,1},{1,0},{0,2},{2,0},{0,4},{4,0},{3,3}}
}
"""


def test_hlo_cost_level_vector_classifies_links():
    from repro.launch import hlo_cost
    w = hlo_cost.analyze_hlo(_LEVEL_HLO, level_sizes=(2, 2, 2),
                             level_names=("chip", "host", "pod"))
    # 2 links per level x 64 bytes; the {3,3} self-pair is free.
    assert w["wire_bytes_by_level_total"] == [128.0, 128.0, 128.0]
    assert w["level_names"] == ["chip", "host", "pod"]
    # Two-level shorthand unchanged: intra = within groups of 4.
    w2 = hlo_cost.analyze_hlo(_LEVEL_HLO, intra_group_size=4)
    assert (w2["wire_bytes_intra_total"],
            w2["wire_bytes_inter_total"]) == (256.0, 128.0)


def test_hlo_cost_rejects_mismatched_level_sizes():
    from repro.launch import hlo_cost
    with pytest.raises(ValueError, match="num_partitions=8"):
        hlo_cost.analyze_hlo(_LEVEL_HLO, level_sizes=(2, 2))


# ---------------------------------------------------------------------------
# Train-path threading (explicit shard_map step + implicit plan_train)
# ---------------------------------------------------------------------------


def test_merge_gradients_plan_matches_flat():
    from repro.core.grad_merge import merge_gradients
    grads = {"w": jax.random.normal(jax.random.key(5), (8, 6)),
             "b": jax.random.normal(jax.random.key(6), (8, 2))}
    plan = MergePlan.parse("chip:2,host:2,pod:2", lane_parallel=True)
    hier = jax.vmap(
        lambda g: merge_gradients(g, "cores", topology=plan),
        axis_name="cores")(grads)
    flat = jax.vmap(
        lambda g: merge_gradients(g, "cores"), axis_name="cores")(grads)
    for k in grads:
        np.testing.assert_allclose(np.asarray(hier[k]), np.asarray(flat[k]),
                                   rtol=1e-5, atol=1e-6)


def test_merge_gradients_mean_uses_topology_axis():
    """A topology pinned to its own axis must drive BOTH the reduction and
    the mean — a mismatch used to silently mis-scale gradients."""
    from repro.core.grad_merge import merge_gradients
    grads = jnp.ones((8, 4))
    topo = ccache.MergeTopology(group_size=4, axis_name="cores")
    out = jax.vmap(
        lambda g: merge_gradients(g, "WRONG_AXIS", topology=topo),
        axis_name="cores")(grads)
    np.testing.assert_allclose(np.asarray(out), np.ones((8, 4)), rtol=1e-6)


def test_train_step_rejects_defer_plans():
    """Gradient merges must complete every step; defer levels would train
    on partially merged gradients."""
    from jax.sharding import AbstractMesh
    from repro.launch.steps import make_train_step
    from repro.configs.base import get_smoke_config
    from repro.models.registry import build_model
    from repro.optim import adamw, constant
    cfg = get_smoke_config("xlstm_125m")
    mesh = AbstractMesh((1, 1), ("data", "model"))
    plan = MergePlan.parse("chip:1:defer")
    with pytest.raises(ValueError, match="defer"):
        make_train_step(build_model(cfg), cfg, adamw(constant(1e-3)), 1,
                        mesh=mesh, merge_topology=plan)


def test_nontrivial_auto_axes_fail_loudly():
    """Partial-auto shard_map aborts XLA's SPMD partitioner fatally on this
    model; the step builder must refuse with an explanation instead."""
    from jax.sharding import AbstractMesh
    from repro.launch.steps import make_train_step
    from repro.configs.base import get_smoke_config
    from repro.models.registry import build_model
    from repro.optim import adamw, constant
    cfg = get_smoke_config("xlstm_125m")
    mesh = AbstractMesh((1, 2), ("data", "model"))
    plan = MergePlan.parse("chip:1")
    with pytest.raises(NotImplementedError, match="partial-auto"):
        make_train_step(build_model(cfg), cfg, adamw(constant(1e-3)), 1,
                        mesh=mesh, merge_topology=plan)


@pytest.mark.slow
def test_three_level_plan_through_both_train_paths():
    """Acceptance: a 3-level chip/host/pod MergePlan runs through BOTH the
    explicit shard_map step and the implicit plan_train path on a forced
    8-device (pod x data) mesh, matching the flat implicit baseline."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs.base import ShapeConfig, get_smoke_config
        from repro.data.pipeline import batch_at, data_config_for
        from repro.launch.steps import make_train_step, plan_train
        from repro.models.module import split_params
        from repro.models.registry import build_model
        from repro.optim import make_optimizer, warmup_cosine
        from repro.sharding.partition import sharding_rules
        from repro.core.merge_plan import MergePlan

        cfg = get_smoke_config("xlstm_125m")
        shape = ShapeConfig("t", 32, 8, "train")
        mesh = jax.make_mesh((2, 4, 1), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        plan = MergePlan.parse("chip:2,host:2,pod:2", lane_parallel=True)
        dcfg = data_config_for(cfg, shape, seed=0)
        batch = jax.tree.map(jnp.asarray, batch_at(dcfg, 0))
        model = build_model(cfg)

        def one_step(merge_plan, implicit):
            p = plan_train(cfg, shape, mesh, merge_plan=merge_plan)
            with mesh, sharding_rules(mesh, p.rules):
                params, _ = split_params(model.init(jax.random.key(0)))
                opt = make_optimizer(cfg, warmup_cosine(3e-4, 100, 10000))
                state = {"params": params, "opt": opt.init(params)}
                if implicit:
                    fn = jax.jit(p.fn, in_shardings=p.in_shardings,
                                 out_shardings=p.out_shardings)
                else:
                    step = make_train_step(model, cfg, opt, 1, mesh=mesh,
                                           merge_topology=merge_plan)
                    fn = jax.jit(step)
                out, metrics = fn(state, batch)
                return (jax.tree.map(np.asarray, out["params"]),
                        float(metrics["loss"]))

        base, loss0 = one_step(None, True)
        impl, loss1 = one_step(plan, True)
        expl, loss2 = one_step(plan, False)
        assert abs(loss0 - loss1) < 5e-3 and abs(loss0 - loss2) < 5e-3, (
            loss0, loss1, loss2)
        for name, variant in (("implicit", impl), ("explicit", expl)):
            for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(variant)):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    atol=3e-2, rtol=3e-2)
        print("BOTH_PATHS_OK")
    """)
    r = subprocess.run([sys.executable, "-c", script], env=ENV,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    assert "BOTH_PATHS_OK" in r.stdout


@pytest.mark.slow
def test_train_cli_merge_topology():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "xlstm-125m",
         "--smoke", "--steps", "3", "--batch", "8", "--seq", "32",
         "--merge-topology", "chip:2,host:2,pod:2", "--merge-lane-parallel",
         "--ckpt-dir", "/tmp/repro_mt_cli_test"],
        env=dict(ENV,
                 XLA_FLAGS="--xla_force_host_platform_device_count=8"),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "loss" in r.stdout


def test_train_cli_merge_topology_mismatch_errors():
    # Pin the device count (the CLI otherwise forces the host platform to
    # the plan's rank count): 8 devices vs a 6-rank plan must be a clear
    # validation error, the real-hardware mismatch scenario.
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "xlstm-125m",
         "--smoke", "--steps", "1", "--merge-topology", "chip:3,pod:2",
         "--ckpt-dir", "/tmp/repro_mt_cli_err"],
        env=dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=8"),
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "product of level sizes" in (r.stderr + r.stdout)

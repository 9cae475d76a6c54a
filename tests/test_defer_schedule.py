"""Schedule-aware deferred commits: the roofline solver, the pending
cascade, and the deferred train path.

Property under test (the paper's merge-on-evict contract, extended to the
optimizer-facing path): a cycle of scheduled deferred commits is
numerically identical to eagerly merging every step and accumulating —
for ADD/MAX/COMPLEX_MUL at the cascade level, and for AdamW-consumed
gradients at the train-step level. Collectives run under
``vmap(axis_name=...)``; the shard_map train path is covered by the slow
subprocess CLI tests at the bottom.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ccache
from repro.core import merge_functions as mf
from repro.core.defer_schedule import DeferSchedule, solve_defer_schedule
from repro.core.merge_plan import MergePlan

ENV = dict(os.environ, PYTHONPATH=os.pathsep.join(
    [os.path.abspath("src"), os.environ.get("PYTHONPATH", "")]))
ENV.pop("XLA_FLAGS", None)  # the train CLI must force its own device count


# ---------------------------------------------------------------------------
# DeferSchedule
# ---------------------------------------------------------------------------


def test_schedule_fixed_and_due_counts():
    s = DeferSchedule.fixed(3, ("host", "pod"))
    assert s.intervals == (3, 3) and s.period == 3
    assert [s.due_count(t) for t in range(1, 7)] == [0, 0, 2, 0, 0, 2]


def test_schedule_nested_due_is_prefix():
    s = DeferSchedule(("host", "pod"), (2, 6))
    assert s.period == 6
    assert [s.due_count(t) for t in range(1, 13)] == \
        [0, 1, 0, 1, 0, 2, 0, 1, 0, 1, 0, 2]


def test_schedule_rejects_non_nested_and_bad_intervals():
    with pytest.raises(ValueError, match="nested"):
        DeferSchedule(("host", "pod"), (2, 3))
    with pytest.raises(ValueError, match="positive"):
        DeferSchedule(("pod",), (0,))
    with pytest.raises(ValueError, match="levels"):
        DeferSchedule(("pod",), (2, 4))


# ---------------------------------------------------------------------------
# Solver
# ---------------------------------------------------------------------------

BWS3 = [50e9, 25e9, 12.5e9]


def test_solver_picks_k_when_deferred_level_dominates():
    plan = MergePlan.parse("chip:4,host:4,pod:2:defer")
    # eager: 1e9/50e9 + 5e8/25e9 = 40ms/1000; pod: 4e8/12.5e9 = 32ms/1000.
    s = solve_defer_schedule(plan, [1e9, 5e8, 4e8], ("chip", "host", "pod"),
                             bandwidths=BWS3)
    # K = ceil(0.032 / (0.5 * 0.04)) = 2
    assert s.intervals == (2,)
    assert s.predicted["per_level"][0]["amortized_bytes_per_step"] == 2e8
    assert s.predicted["top_amortization_x"] == 2


def test_solver_compute_bound_step_needs_no_deferral():
    plan = MergePlan.parse("chip:4,host:4,pod:2:defer")
    s = solve_defer_schedule(plan, [1e9, 5e8, 4e8], ("chip", "host", "pod"),
                             bandwidths=BWS3, compute_s=10.0)
    assert s.intervals == (1,)


def test_solver_zero_traffic_level_gets_k1():
    plan = MergePlan.parse("chip:4,host:4,pod:2:defer")
    s = solve_defer_schedule(plan, [1e9, 5e8, 0.0], ("chip", "host", "pod"),
                             bandwidths=BWS3)
    assert s.intervals == (1,)


def test_solver_clamps_to_k_max():
    plan = MergePlan.parse("chip:4,host:4,pod:2:defer")
    s = solve_defer_schedule(plan, [1.0, 1.0, 1e12], ("chip", "host", "pod"),
                             bandwidths=BWS3, k_max=16)
    assert s.intervals == (16,)


def test_solver_rejects_empty_interval_window():
    """Regression: k_max below k_min (e.g. k_max=0) used to ESCAPE the
    clamp — the nested rounding ``max(prev_k, (k_max // prev_k) * prev_k)``
    returned prev_k > k_max and the solver silently handed back a schedule
    outside its own window.  It must refuse the geometry loudly."""
    plan = MergePlan.parse("chip:4,host:4,pod:2:defer")
    with pytest.raises(ValueError, match="k_max"):
        solve_defer_schedule(plan, [1.0, 1.0, 1e12], ("chip", "host", "pod"),
                             bandwidths=BWS3, k_max=0)
    with pytest.raises(ValueError, match="k_min"):
        solve_defer_schedule(plan, [1.0, 1.0, 1e12], ("chip", "host", "pod"),
                             bandwidths=BWS3, k_min=0)
    with pytest.raises(ValueError, match="k_max"):
        solve_defer_schedule(plan, [1.0, 1.0, 1e12], ("chip", "host", "pod"),
                             bandwidths=BWS3, k_min=8, k_max=4)


def test_solver_nested_clamp_never_exceeds_k_max():
    """The k_max clamp must respect nesting: when no multiple of the inner
    interval fits under k_max, raise rather than exceed the cap."""
    plan = MergePlan.parse("chip:2,host:2:defer,pod:2:defer")
    # host solves to K=3 (30ms vs 10ms target); pod wants 7 -> nest to 9,
    # but k_max=5 admits no positive multiple of 3... of 3 there is 3 <= 5,
    # so this clamps to 3 — legal.
    s = solve_defer_schedule(plan, [1e9, 7.5e8, 8e8], ("chip", "host", "pod"),
                             bandwidths=BWS3, k_max=5)
    assert s.intervals == (3, 3)
    assert max(s.intervals) <= 5
    # k_max=2 < host's own minimum nested step: no schedule exists
    with pytest.raises(ValueError, match="k_max"):
        solve_defer_schedule(plan, [1e9, 7.5e8, 8e8],
                             ("chip", "host", "pod"),
                             bandwidths=BWS3, k_min=3, k_max=2)


def test_solver_nests_outer_interval_on_inner():
    plan = MergePlan.parse("chip:2,host:2:defer,pod:2:defer")
    # host t = 7.5e8/25e9 = 30ms/1000 -> K=ceil(0.03/0.01)=3;
    # pod t = 8e8/12.5e9 = 64ms/1000 -> raw ceil(0.064/0.01)=7 -> nest to 9.
    s = solve_defer_schedule(plan, [1e9, 7.5e8, 8e8], ("chip", "host", "pod"),
                             bandwidths=BWS3)
    assert s.intervals[0] == 3
    assert s.intervals[1] % s.intervals[0] == 0
    assert s.intervals == (3, 9)


def test_solver_accepts_fabric_rates():
    from benchmarks.simulator import default_fabric
    plan = MergePlan.parse("chip:4,host:4,pod:2:defer")
    s = solve_defer_schedule(plan, [1e9, 5e8, 4e8], ("chip", "host", "pod"),
                             fabric=default_fabric(scale=4))
    assert s.level_names == ("pod",) and s.intervals[0] >= 1


def test_solver_requires_deferred_levels_and_matching_names():
    with pytest.raises(ValueError, match="no deferred"):
        solve_defer_schedule(MergePlan.parse("chip:4,pod:2"),
                             [1e9, 4e8], ("chip", "pod"), bandwidths=BWS3[:2])
    with pytest.raises(ValueError, match="missing"):
        solve_defer_schedule(MergePlan.parse("chip:4,pod:2:defer"),
                             [1e9, 4e8], ("chip", "WRONG"),
                             bandwidths=BWS3[:2])


def test_dci_bytes_derived_from_level_vector():
    """dryrun's DCI share comes from the vector, not a defaulted-zero key."""
    from repro.launch.hlo_analysis import dci_bytes
    assert dci_bytes([1e9, 5e8, 4e8], ("chip", "host", "pod")) == 4e8
    assert dci_bytes([1e9, 5e8], ("chip", "host")) == 0.0  # single-pod: ICI only


# ---------------------------------------------------------------------------
# The pending cascade: scheduled commits ≡ eager merges (property-style)
# ---------------------------------------------------------------------------


def _cascade_run(merge, size, plan, schedule, upds):
    """Run T scheduled steps under vmap; returns the list of full-commit
    results (one per cycle) and the final pendings."""
    n_def = len(ccache.deferred_stages_of(plan, size))
    like = jax.tree.map(lambda x: x[0], upds[0])
    pends = tuple(
        jax.vmap(lambda _: merge.tree_identity(like))(jnp.zeros(size))
        for _ in range(n_def))
    commits = []
    for t in range(len(upds)):
        due = schedule.due_count(t + 1)

        def step(g, *p):
            new_p, settled = ccache.defer_cascade(g, list(p), due, "cores",
                                                  merge, plan)
            return tuple(new_p), settled

        pends, settled = jax.vmap(step, axis_name="cores")(upds[t], *pends)
        if due == n_def:
            commits.append(settled)
    return commits, pends


def _eager_cycle(merge, upds, lo, hi):
    """combine over steps [lo, hi) of the flat per-step full merge."""
    acc = None
    for t in range(lo, hi):
        m = jax.vmap(lambda v: ccache.tree_merge(v, "cores", merge),
                     axis_name="cores")(upds[t])
        acc = m if acc is None else merge.tree_combine(acc, m)
    return acc


CASCADE_PLANS = [
    (8, "chip:2,host:2,pod:2:defer", (2,)),
    (8, "chip:2,host:2:defer,pod:2:defer", (2, 4)),
    (12, "chip:2,host:3,pod:2:defer", (3,)),
    (8, "chip:2,host:2:defer,pod:2:defer", (1, 3)),
]


@settings(max_examples=8, deadline=None)
@given(lane=st.booleans(),
       seed=st.integers(min_value=0, max_value=10**6),
       case=st.sampled_from(CASCADE_PLANS))
def test_property_cascade_add_equals_eager(lane, seed, case):
    size, spec, intervals = case
    plan = MergePlan.parse(spec, lane_parallel=lane)
    names = tuple(s.name for s in ccache.deferred_stages_of(plan, size))
    sched = DeferSchedule(names, intervals)
    T = 2 * sched.period
    upds = jax.random.normal(jax.random.key(seed), (T, size, 5))
    commits, _ = _cascade_run(mf.ADD, size, plan, sched, upds)
    assert len(commits) == 2
    for c, (lo, hi) in zip(commits, [(0, sched.period),
                                     (sched.period, T)]):
        want = _eager_cycle(mf.ADD, upds, lo, hi)
        # the settled value is replicated: every rank must agree
        np.testing.assert_allclose(np.asarray(c), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(lane=st.booleans(),
       seed=st.integers(min_value=0, max_value=10**6),
       case=st.sampled_from(CASCADE_PLANS))
def test_property_cascade_max_bitwise_equals_eager(lane, seed, case):
    size, spec, intervals = case
    plan = MergePlan.parse(spec, lane_parallel=lane)
    names = tuple(s.name for s in ccache.deferred_stages_of(plan, size))
    sched = DeferSchedule(names, intervals)
    T = sched.period
    upds = jax.random.normal(jax.random.key(seed), (T, size, 4))
    commits, _ = _cascade_run(mf.MAX, size, plan, sched, upds)
    np.testing.assert_array_equal(
        np.asarray(commits[0]), np.asarray(_eager_cycle(mf.MAX, upds, 0, T)))


@settings(max_examples=6, deadline=None)
@given(lane=st.booleans(), seed=st.integers(min_value=0, max_value=10**6))
def test_property_cascade_custom_software_combine(lane, seed):
    """The paper's headline flexibility: a software combine (complex
    product, structured wire atom) survives the nested cascade."""
    plan = MergePlan.parse("chip:2,host:2:defer,pod:2:defer",
                           lane_parallel=lane)
    sched = DeferSchedule(("host", "pod"), (2, 4))
    upds = (jax.random.normal(jax.random.key(seed), (4, 8, 3, 2)) * 0.2
            + jnp.asarray([1.0, 0.0]))
    commits, _ = _cascade_run(mf.COMPLEX_MUL, 8, plan, sched, upds)
    np.testing.assert_allclose(
        np.asarray(commits[0]),
        np.asarray(_eager_cycle(mf.COMPLEX_MUL, upds, 0, 4)),
        rtol=1e-4, atol=1e-4)


def test_cascade_partial_commit_returns_no_settled_value():
    plan = MergePlan.parse("chip:2,host:2:defer,pod:2:defer")
    upds = jax.random.normal(jax.random.key(0), (8, 3))

    def step(g, p0, p1):
        new_p, settled = ccache.defer_cascade(g, [p0, p1], 1, "cores",
                                              mf.ADD, plan)
        assert settled is None  # only the inner level committed
        return tuple(new_p)

    z = jnp.zeros((8, 3))
    p0, p1 = jax.vmap(step, axis_name="cores")(upds, z, z)
    # the inner pending was reset, its aggregate moved up to the outer one
    np.testing.assert_allclose(np.asarray(p0), 0.0)
    assert float(jnp.abs(p1).sum()) > 0


def test_cascade_validates_pending_count_and_due():
    plan = MergePlan.parse("chip:2,pod:2:defer")
    z = jnp.zeros((4, 3))
    with pytest.raises(ValueError, match="pendings"):
        jax.vmap(lambda g: ccache.defer_cascade(g, [g, g], 0, "cores",
                                                mf.ADD, plan),
                 axis_name="cores")(z)
    with pytest.raises(ValueError, match="due"):
        jax.vmap(lambda g: ccache.defer_cascade(g, [g], 2, "cores",
                                                mf.ADD, plan),
                 axis_name="cores")(z)
    with pytest.raises(ValueError, match="no deferred"):
        jax.vmap(lambda g: ccache.defer_cascade(
            g, [], 0, "cores", mf.ADD, MergePlan.parse("chip:2,pod:2")),
            axis_name="cores")(z)


# ---------------------------------------------------------------------------
# Optimizer-facing equivalence: deferred-K training ≡ K-step accumulation
# ---------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(k=st.integers(min_value=1, max_value=3),
       lane=st.booleans(),
       seed=st.integers(min_value=0, max_value=10**6))
def test_property_deferred_adamw_equals_accumulated_eager(k, lane, seed):
    """K scheduled gradient commits consumed by AdamW must match K eager
    full merges accumulated and averaged — the train path's numerical
    contract (correct loss/weight scaling included)."""
    from repro.optim.optimizers import adamw
    from repro.optim.schedules import constant

    size = 8
    plan = MergePlan.parse("chip:2,host:2,pod:2:defer", lane_parallel=lane)
    sched = DeferSchedule.fixed(k, ("pod",))
    T = 2 * k
    key = jax.random.key(seed)
    kp, kg = jax.random.split(key)
    params = {"w": jax.random.normal(kp, (6,)),
              "b": jax.random.normal(kp, (2,))}
    grads_t = [
        {"w": jax.random.normal(jax.random.fold_in(kg, t), (size, 6)),
         "b": jax.random.normal(jax.random.fold_in(kg, 1000 + t), (size, 2))}
        for t in range(T)]
    opt = adamw(constant(1e-2))

    # -- deferred path: the cascade, scaled like the train step ------------
    p_def = params
    opt_def = opt.init(params)
    pends = (jax.tree.map(lambda x: jnp.zeros((size,) + x.shape[1:]),
                          grads_t[0]),)
    for t in range(T):
        due = sched.due_count(t + 1)

        def step(g, p0):
            new_p, settled = ccache.defer_cascade(g, [p0], due, "cores",
                                                  mf.ADD, plan)
            return tuple(new_p), settled

        pends, settled = jax.vmap(step, axis_name="cores")(grads_t[t],
                                                           *pends)
        if due == 1:
            grads = jax.tree.map(lambda s: s[0] / (size * k), settled)
            p_def, opt_def, _ = opt.step(p_def, grads, opt_def)

    # -- eager baseline: full merge every step, accumulate K, step once ----
    p_ref = params
    opt_ref = opt.init(params)
    acc = jax.tree.map(jnp.zeros_like, params)
    for t in range(T):
        merged = jax.tree.map(lambda g: g.sum(0) / size, grads_t[t])
        acc = jax.tree.map(jnp.add, acc, merged)
        if (t + 1) % k == 0:
            grads = jax.tree.map(lambda a: a / k, acc)
            p_ref, opt_ref, _ = opt.step(p_ref, grads, opt_ref)
            acc = jax.tree.map(jnp.zeros_like, params)

    for a, b in zip(jax.tree.leaves(p_def), jax.tree.leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Train-path threading (step builder; the CLI runs in the slow tests)
# ---------------------------------------------------------------------------


def _smoke_pieces():
    from repro.configs.base import get_smoke_config
    from repro.models.registry import build_model
    from repro.optim import adamw, constant
    cfg = get_smoke_config("xlstm_125m")
    return cfg, build_model(cfg), adamw(constant(1e-3))


def test_train_step_defer_builds_variants():
    from jax.sharding import AbstractMesh
    from repro.launch.steps import DeferredTrainStep, make_train_step
    cfg, model, opt = _smoke_pieces()
    mesh = AbstractMesh((8, 1), ("data", "model"))
    plan = MergePlan.parse("chip:2,host:2,pod:2:defer")
    sched = DeferSchedule.fixed(3, ("pod",))
    step = make_train_step(model, cfg, opt, 1, mesh=mesh,
                           merge_topology=plan, defer_schedule=sched)
    assert isinstance(step, DeferredTrainStep)
    assert len(step.variants) == 2          # accumulate + full commit
    assert step.dp == 8 and step.deferred_names == ("pod",)
    specs = jax.eval_shape(
        step.init_defer_state,
        {"w": jax.ShapeDtypeStruct((4,), jnp.float32)})
    assert specs["pending"][0]["w"].shape == (8, 4)


def test_train_step_defer_schedule_mismatch_raises():
    from jax.sharding import AbstractMesh
    from repro.launch.steps import make_train_step
    cfg, model, opt = _smoke_pieces()
    mesh = AbstractMesh((8, 1), ("data", "model"))
    plan = MergePlan.parse("chip:2,host:2,pod:2:defer")
    with pytest.raises(ValueError, match="do not match"):
        make_train_step(model, cfg, opt, 1, mesh=mesh, merge_topology=plan,
                        defer_schedule=DeferSchedule.fixed(3,
                                                           ("host", "pod")))


def test_train_step_schedule_without_defer_plan_raises():
    from jax.sharding import AbstractMesh
    from repro.launch.steps import make_train_step
    cfg, model, opt = _smoke_pieces()
    mesh = AbstractMesh((8, 1), ("data", "model"))
    with pytest.raises(ValueError, match="no :defer"):
        make_train_step(model, cfg, opt, 1, mesh=mesh,
                        merge_topology=MergePlan.parse("chip:4,pod:2"),
                        defer_schedule=DeferSchedule.fixed(2, ("pod",)))


def test_plan_train_threads_defer_state():
    from jax.sharding import AbstractMesh
    from repro.configs.base import ShapeConfig
    from repro.launch.steps import plan_train
    cfg, _, _ = _smoke_pieces()
    mesh = AbstractMesh((8, 1), ("data", "model"))
    shape = ShapeConfig("t", 32, 8, "train")
    lp = plan_train(cfg, shape, mesh,
                    merge_plan=MergePlan.parse("chip:2,host:2,pod:2:defer"),
                    defer_schedule=DeferSchedule.fixed(4, ("pod",)))
    assert lp.defer_step is not None
    assert lp.defer_step.schedule.period == 4
    assert "defer" in lp.in_specs[0]
    assert "defer" in lp.in_shardings[0]


@pytest.mark.slow
def test_train_cli_merge_defer_fixed_k():
    """Acceptance: the train CLI runs a :defer topology end-to-end with a
    fixed commit interval (forcing its own host device count)."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "xlstm-125m",
         "--smoke", "--steps", "4", "--batch", "8", "--seq", "32",
         "--merge-topology", "chip:2,host:2,pod:2:defer",
         "--merge-defer", "2", "--merge-lane-parallel",
         "--ckpt-dir", "/tmp/repro_defer_cli_fixed"],
        env=ENV, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "merge-defer schedule" in r.stdout
    assert "loss" in r.stdout


@pytest.mark.slow
def test_train_cli_merge_defer_auto():
    """--merge-defer auto compiles the eager twin, prints the solved
    schedule + predicted savings, and trains."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "xlstm-125m",
         "--smoke", "--steps", "2", "--batch", "8", "--seq", "32",
         "--merge-topology", "chip:2,host:2,pod:2:defer",
         "--merge-defer", "auto",
         "--ckpt-dir", "/tmp/repro_defer_cli_auto"],
        env=ENV, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "merge-defer schedule" in r.stdout
    assert "K=" in r.stdout


def test_train_cli_defer_without_schedule_rejected():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "xlstm-125m",
         "--smoke", "--steps", "1",
         "--merge-topology", "chip:2,host:2,pod:2:defer",
         "--ckpt-dir", "/tmp/repro_defer_cli_err"],
        env=ENV, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "--merge-defer" in (r.stderr + r.stdout)


@pytest.mark.slow
def test_deferred_k1_matches_eager_explicit_train_path():
    """K=1 defers nothing: the deferred train step must reproduce the eager
    explicit shard_map step's parameters step-for-step on a real mesh."""
    import textwrap
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs.base import ShapeConfig, get_smoke_config
        from repro.data.pipeline import batch_at, data_config_for
        from repro.core.defer_schedule import DeferSchedule
        from repro.core.merge_plan import MergePlan
        from repro.launch.steps import make_train_step
        from repro.models.module import split_params
        from repro.models.registry import build_model
        from repro.optim import make_optimizer, warmup_cosine
        from repro.sharding.partition import sharding_rules
        from repro.launch.steps import lowering_rules

        cfg = get_smoke_config("xlstm_125m")
        shape = ShapeConfig("t", 32, 8, "train")
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(8, 1)
        rules = lowering_rules(cfg, shape, mesh)
        model = build_model(cfg)
        plan = MergePlan.parse("chip:2,host:2,pod:2:defer",
                               lane_parallel=True)
        eager_plan = MergePlan.parse("chip:2,host:2,pod:2",
                                     lane_parallel=True)
        dcfg = data_config_for(cfg, shape, seed=0)
        batches = [jax.tree.map(jnp.asarray, batch_at(dcfg, i))
                   for i in range(3)]

        def run(deferred):
            opt = make_optimizer(cfg, warmup_cosine(3e-4, 100, 10000))
            with mesh, sharding_rules(mesh, rules):
                params, _ = split_params(model.init(jax.random.key(0)))
                state = {"params": params, "opt": opt.init(params)}
                if deferred:
                    step = make_train_step(
                        model, cfg, opt, 1, mesh=mesh, merge_topology=plan,
                        defer_schedule=DeferSchedule.fixed(1, ("pod",)))
                    state["defer"] = step.init_defer_state(params)
                    fn = step.jit()
                else:
                    step = make_train_step(model, cfg, opt, 1, mesh=mesh,
                                           merge_topology=eager_plan)
                    fn = jax.jit(step)
                for b in batches:
                    state, metrics = fn(state, b)
                return (jax.tree.map(np.asarray, state["params"]),
                        float(metrics["loss"]))

        p_eager, l_eager = run(False)
        p_defer, l_defer = run(True)
        assert abs(l_eager - l_defer) < 1e-4, (l_eager, l_defer)
        for a, b in zip(jax.tree.leaves(p_eager), jax.tree.leaves(p_defer)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=1e-5, rtol=1e-5)
        print("DEFER_K1_MATCHES_EAGER")
    """)
    r = subprocess.run([sys.executable, "-c", script], env=ENV,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    assert "DEFER_K1_MATCHES_EAGER" in r.stdout


# ---------------------------------------------------------------------------
# AdaptiveDeferSchedule: load-driven K
# ---------------------------------------------------------------------------

def test_adaptive_schedule_tracks_ingest_rate():
    """Heavier measured ingest grows the per-tick compute bound, so the
    commit amortizes more easily and K moves DOWN; idle traffic drifts it
    back up toward k_max."""
    from repro.core.defer_schedule import AdaptiveDeferSchedule
    plan = MergePlan.parse("chip:2:defer,pod:2:defer")
    sched = AdaptiveDeferSchedule(plan, [1e6, 4e6], ("chip", "pod"),
                                  base_compute_s=1e-6, per_update_s=1e-6,
                                  k_max=16)
    assert sched.max_period == 16
    k_idle = sched.period
    assert k_idle == 16                      # nothing to hide behind
    for _ in range(50):
        sched.observe(5000)
    for _ in range(sched.period):            # reach a cycle boundary
        sched.due_count(0)
    k_busy = sched.period
    assert k_busy < k_idle
    assert len(set(sched.intervals)) == 1    # uniform, all-or-nothing
    # the phase is internal: due fires all levels exactly at the boundary
    fires = [sched.due_count(0) for _ in range(3 * sched.period)]
    assert set(fires) <= {0, len(sched.level_names)}
    assert fires.count(len(sched.level_names)) == 3
    sched.reset()
    assert sched.period == k_idle            # load history forgotten
    d = sched.as_dict()
    assert d["adaptive"]["k_max"] == 16 and d["adaptive"]["n_resolves"] >= 4
    assert "adaptive" in sched.describe() or "ema" in sched.describe()


def test_adaptive_schedule_validates_inputs():
    from repro.core.defer_schedule import AdaptiveDeferSchedule
    plan = MergePlan.parse("chip:2:defer,pod:2:defer")
    with pytest.raises(ValueError, match="ema_alpha"):
        AdaptiveDeferSchedule(plan, [1e6, 4e6], ("chip", "pod"),
                              ema_alpha=0.0)
    with pytest.raises(ValueError, match=">= 0"):
        AdaptiveDeferSchedule(plan, [1e6, 4e6], ("chip", "pod"),
                              per_update_s=-1.0)

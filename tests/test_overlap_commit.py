"""Overlapped deferred commits: launch/land pipeline semantics.

Properties under test (the tentpole contract):

* an overlapped commit cycle consumed by AdamW is *exactly* K-step
  gradient accumulation applied with a one-step delay — the optimizer
  update that the serialized path applies after step cK lands after step
  cK+1, with the identical cycle-mean gradient (matching PR 3's
  eager-equivalence style);
* the final flush drains everything outstanding — an in-flight launched
  cycle and/or a trailing partial cycle — so an N-step run with
  ``N % K != 0`` loses zero gradient mass versus the eager twin;
* the train-step builders thread the in-flight buffer through both train
  paths (``make_train_step`` land variants + ``plan_train`` shardings).

Collectives run under ``vmap(axis_name=...)``; the real shard_map train
path is covered by the slow subprocess tests at the bottom.
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
from repro.core.defer_schedule import DeferSchedule, solve_defer_schedule
from repro.core.merge_plan import MergePlan

ENV = dict(os.environ, PYTHONPATH=os.pathsep.join(
    [os.path.abspath("src"), os.environ.get("PYTHONPATH", "")]))
ENV.pop("XLA_FLAGS", None)  # subprocesses force their own device count


# ---------------------------------------------------------------------------
# Schedule / solver plumbing
# ---------------------------------------------------------------------------


def test_schedule_overlap_flag_round_trips():
    s = DeferSchedule.fixed(3, ("pod",), overlap=True)
    assert s.overlap
    assert s.as_dict()["overlap"] is True
    assert "overlapped" in s.describe()
    assert not DeferSchedule.fixed(3, ("pod",)).overlap


BWS3 = [50e9, 25e9, 12.5e9]


def test_solver_overlap_hides_top_level_and_lowers_k():
    plan = MergePlan.parse("chip:4,host:4,pod:2:defer")
    vec = [1e9, 5e8, 4e8]  # pod t = 32ms/1000; eager wire = 40ms/1000
    serial = solve_defer_schedule(plan, vec, ("chip", "host", "pod"),
                                  bandwidths=BWS3, compute_s=0.02)
    ovl = solve_defer_schedule(plan, vec, ("chip", "host", "pod"),
                               bandwidths=BWS3, compute_s=0.02, overlap=True)
    assert ovl.overlap and not serial.overlap
    assert ovl.intervals[-1] <= serial.intervals[-1]
    top = ovl.predicted["per_level"][-1]
    assert top["hidden_s"] == pytest.approx(0.02)
    assert top["exposed_s"] == pytest.approx(0.012)


def test_solver_overlap_fully_hidden_commits_every_step():
    plan = MergePlan.parse("chip:4,host:4,pod:2:defer")
    s = solve_defer_schedule(plan, [1e9, 5e8, 4e8], ("chip", "host", "pod"),
                             bandwidths=BWS3, compute_s=10.0, overlap=True)
    assert s.intervals == (1,)
    assert s.predicted["per_level"][-1]["exposed_s"] == pytest.approx(0.0)


def test_solver_overlap_without_compute_matches_serial():
    """No compute to hide behind -> the overlap solver degenerates to the
    serialized one (hidden budget 0)."""
    plan = MergePlan.parse("chip:4,host:4,pod:2:defer")
    vec = [1e9, 5e8, 4e8]
    serial = solve_defer_schedule(plan, vec, ("chip", "host", "pod"),
                                  bandwidths=BWS3)
    ovl = solve_defer_schedule(plan, vec, ("chip", "host", "pod"),
                               bandwidths=BWS3, overlap=True)
    assert ovl.intervals == serial.intervals


def test_solver_overlap_only_hides_top_level():
    """Inner deferred levels still commit inline: at the same compute
    bound, only the TOP level's K shrinks from the overlap budget."""
    plan = MergePlan.parse("chip:2,host:2:defer,pod:2:defer")
    vec = [1e9, 7.5e8, 8e8]   # host t=30ms, pod t=64ms (per 1000)
    serial = solve_defer_schedule(plan, vec, ("chip", "host", "pod"),
                                  bandwidths=BWS3, compute_s=0.03)
    ovl = solve_defer_schedule(plan, vec, ("chip", "host", "pod"),
                               bandwidths=BWS3, compute_s=0.03, overlap=True)
    # host (inner) interval identical at the shared bound; pod (top) drops
    # because only its exposed 34ms remainder needs amortizing.
    assert ovl.intervals[0] == serial.intervals[0] == 2
    assert ovl.intervals[-1] < serial.intervals[-1]
    assert ovl.intervals[-1] % ovl.intervals[0] == 0


# ---------------------------------------------------------------------------
# The pipeline property: overlapped commits == K-step accumulation with a
# one-step delay (AdamW end-to-end)
# ---------------------------------------------------------------------------


def _overlap_run(plan, k, size, grads_t, opt, params):
    """Run the overlapped pipeline at the cascade level: launch on every
    full-commit step, land (+ AdamW step) one step later, flush at the
    end. Returns the params history (entry t = params after step t) and
    the final flushed params."""
    sched = DeferSchedule.fixed(k, ("pod",), overlap=True)
    opt_state = opt.init(params)
    pends = (jax.tree.map(lambda x: jnp.zeros((size,) + x.shape[1:]),
                          grads_t[0]),)
    inflight = jax.tree.map(lambda x: jnp.zeros((size,) + x.shape[1:]),
                            grads_t[0])
    history = []
    T = len(grads_t)
    for t in range(1, T + 1):
        due = sched.due_count(t)
        land = t > 1 and sched.due_count(t - 1) == 1

        def step(g, inf, p0):
            new_p, new_inf, landed = ccache.overlap_cascade(
                g, [p0], inf, due, land, "cores", mf.ADD, plan)
            return tuple(new_p), new_inf, landed

        pends, inflight, landed = jax.vmap(step, axis_name="cores")(
            grads_t[t - 1], inflight, *pends)
        if land:
            grads = jax.tree.map(lambda s: s[0] / (size * k), landed)
            params, opt_state, _ = opt.step(params, grads, opt_state)
        history.append(jax.tree.map(np.asarray, params))
    # Final flush: the last cycle launched at t = T but never landed.
    if sched.due_count(T) == 1:
        landed = jax.vmap(
            lambda x: ccache.settle_inflight(x, "cores", mf.ADD, plan),
            axis_name="cores")(inflight)
        grads = jax.tree.map(lambda s: s[0] / (size * k), landed)
        params, opt_state, _ = opt.step(params, grads, opt_state)
    return history, jax.tree.map(np.asarray, params)


def _eager_run(k, size, grads_t, opt, params):
    """The eager twin: full merge every step, accumulate K, step AdamW at
    every cycle boundary. Returns params history and finals."""
    opt_state = opt.init(params)
    acc = jax.tree.map(jnp.zeros_like, params)
    history = []
    for t in range(1, len(grads_t) + 1):
        merged = jax.tree.map(lambda g: g.sum(0) / size, grads_t[t - 1])
        acc = jax.tree.map(jnp.add, acc, merged)
        if t % k == 0:
            grads = jax.tree.map(lambda a: a / k, acc)
            params, opt_state, _ = opt.step(params, grads, opt_state)
            acc = jax.tree.map(jnp.zeros_like, params)
        history.append(jax.tree.map(np.asarray, params))
    return history, jax.tree.map(np.asarray, params)


def _tree_eq(a, b, **kw):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), **kw)


@settings(max_examples=6, deadline=None)
@given(k=st.integers(min_value=1, max_value=3),
       lane=st.booleans(),
       seed=st.integers(min_value=0, max_value=10**6))
def test_property_overlap_adamw_is_one_step_stale_accumulation(k, lane,
                                                               seed):
    """The acceptance property: the overlapped pipeline's AdamW trajectory
    is the eager K-step-accumulation trajectory shifted by exactly one
    step — params after an overlapped step t equal the eager twin's after
    step t-1 whenever a commit is in flight, and the final flush closes
    the gap entirely."""
    from repro.optim.optimizers import adamw
    from repro.optim.schedules import constant

    size = 8
    plan = MergePlan.parse("chip:2,host:2,pod:2:defer", lane_parallel=lane)
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

    ovl_hist, ovl_final = _overlap_run(plan, k, size, grads_t, opt, params)
    ref_hist, ref_final = _eager_run(k, size, grads_t, opt, params)

    for t in range(1, T + 1):
        if t % k == 0:
            # Launch step: the eager twin has already applied this cycle's
            # update; the overlapped path has not (it is in flight) —
            # one-step-stale by exactly one optimizer application.
            _tree_eq(ovl_hist[t - 1], ref_hist[t - 2] if t >= 2
                     else jax.tree.map(np.asarray, params),
                     rtol=1e-5, atol=1e-6)
        else:
            # Off-commit steps: both paths hold the same params (every
            # earlier cycle has landed).
            _tree_eq(ovl_hist[t - 1], ref_hist[t - 1],
                     rtol=1e-5, atol=1e-6)
    # After the flush, zero gradient mass is outstanding: finals agree.
    _tree_eq(ovl_final, ref_final, rtol=1e-5, atol=1e-6)


@settings(max_examples=6, deadline=None)
@given(k=st.integers(min_value=2, max_value=4),
       m=st.integers(min_value=1, max_value=3),
       seed=st.integers(min_value=0, max_value=10**6))
def test_property_flush_partial_cycle_loses_no_gradient_mass(k, m, seed):
    """N = 2k + m steps with m < k: the trailing partial cycle never
    reaches a commit boundary, but the flush settles it on the mean of its
    m accumulated gradients — matching an eager twin that does the same."""
    from repro.optim.optimizers import adamw
    from repro.optim.schedules import constant

    m = min(m, k - 1)
    size = 8
    plan = MergePlan.parse("chip:2,host:2,pod:2:defer", lane_parallel=True)
    sched = DeferSchedule.fixed(k, ("pod",))
    T = 2 * k + m
    key = jax.random.key(seed)
    kp, kg = jax.random.split(key)
    params = {"w": jax.random.normal(kp, (5,))}
    grads_t = [{"w": jax.random.normal(jax.random.fold_in(kg, t), (size, 5))}
               for t in range(T)]
    opt = adamw(constant(1e-2))

    # Deferred path + flush of the trailing partial cycle.
    p_def, opt_def = params, opt.init(params)
    pends = (jnp.zeros((size, 5)),)
    for t in range(1, T + 1):
        due = sched.due_count(t)

        def step(g, p0):
            new_p, settled = ccache.defer_cascade(g["w"], [p0], due, "cores",
                                                  mf.ADD, plan)
            return tuple(new_p), settled

        pends, settled = jax.vmap(step, axis_name="cores")(grads_t[t - 1],
                                                           *pends)
        if due == 1:
            grads = {"w": settled[0] / (size * k)}
            p_def, opt_def, _ = opt.step(p_def, grads, opt_def)
    # flush: settle the m-step partial cycle with a zero delta, mean over m
    def flush_step(p0):
        new_p, settled = ccache.defer_cascade(jnp.zeros_like(p0), [p0], 1,
                                              "cores", mf.ADD, plan)
        return settled

    settled = jax.vmap(flush_step, axis_name="cores")(pends[0])
    grads = {"w": settled[0] / (size * m)}
    p_def, opt_def, _ = opt.step(p_def, grads, opt_def)

    # Eager twin: accumulate, step every k, final partial step on mean(m).
    p_ref, opt_ref = params, opt.init(params)
    acc = jax.tree.map(jnp.zeros_like, params)
    since = 0
    for t in range(1, T + 1):
        merged = jax.tree.map(lambda g: g.sum(0) / size, grads_t[t - 1])
        acc = jax.tree.map(jnp.add, acc, merged)
        since += 1
        if t % k == 0:
            p_ref, opt_ref, _ = opt.step(
                p_ref, jax.tree.map(lambda a: a / since, acc), opt_ref)
            acc = jax.tree.map(jnp.zeros_like, params)
            since = 0
    assert since == m
    p_ref, opt_ref, _ = opt.step(
        p_ref, jax.tree.map(lambda a: a / since, acc), opt_ref)

    _tree_eq(p_def, p_ref, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Train-path threading (step builders; CLI runs in the slow tests)
# ---------------------------------------------------------------------------


def _smoke_pieces():
    from repro.configs.base import get_smoke_config
    from repro.models.registry import build_model
    from repro.optim import adamw, constant
    cfg = get_smoke_config("xlstm_125m")
    return cfg, build_model(cfg), adamw(constant(1e-3))


def test_train_step_overlap_builds_land_variants():
    from jax.sharding import AbstractMesh
    from repro.launch.steps import DeferredTrainStep, make_train_step
    cfg, model, opt = _smoke_pieces()
    mesh = AbstractMesh((8, 1), ("data", "model"))
    plan = MergePlan.parse("chip:2,host:2,pod:2:defer")
    sched = DeferSchedule.fixed(3, ("pod",), overlap=True)
    step = make_train_step(model, cfg, opt, 1, mesh=mesh,
                           merge_topology=plan, defer_schedule=sched)
    assert isinstance(step, DeferredTrainStep)
    assert step.overlap
    assert len(step.variants) == 2
    assert step.land_variants is not None and len(step.land_variants) == 2
    specs = jax.eval_shape(
        step.init_defer_state,
        {"w": jax.ShapeDtypeStruct((4,), jnp.float32)})
    assert specs["pending"][0]["w"].shape == (8, 4)
    assert specs["inflight"]["w"].shape == (8, 4)


def test_train_step_overlap_land_dispatch():
    """land_due fires exactly on the step after a full-commit launch."""
    from jax.sharding import AbstractMesh
    from repro.launch.steps import make_train_step
    cfg, model, opt = _smoke_pieces()
    mesh = AbstractMesh((8, 1), ("data", "model"))
    plan = MergePlan.parse("chip:2,host:2,pod:2:defer")
    step = make_train_step(
        model, cfg, opt, 1, mesh=mesh, merge_topology=plan,
        defer_schedule=DeferSchedule.fixed(2, ("pod",), overlap=True))

    def at(t):
        state = {"defer": {"t": jnp.asarray(t, jnp.int32)}}
        return step.due(state), step.land_due(state)

    # t completed steps; the step being taken is t+1.
    assert at(0) == (0, False)   # step 1: accumulate
    assert at(1) == (1, False)   # step 2: launch
    assert at(2) == (0, True)    # step 3: land cycle 1
    assert at(3) == (1, False)   # step 4: launch cycle 2
    assert at(4) == (0, True)    # step 5: land cycle 2


def test_train_step_no_overlap_has_no_land_variants():
    from jax.sharding import AbstractMesh
    from repro.launch.steps import make_train_step
    cfg, model, opt = _smoke_pieces()
    mesh = AbstractMesh((8, 1), ("data", "model"))
    plan = MergePlan.parse("chip:2,host:2,pod:2:defer")
    step = make_train_step(
        model, cfg, opt, 1, mesh=mesh, merge_topology=plan,
        defer_schedule=DeferSchedule.fixed(2, ("pod",)))
    assert not step.overlap
    assert step.land_variants is None
    specs = jax.eval_shape(
        step.init_defer_state,
        {"w": jax.ShapeDtypeStruct((4,), jnp.float32)})
    assert "inflight" not in specs


def test_plan_train_threads_inflight_shardings():
    from jax.sharding import AbstractMesh
    from repro.configs.base import ShapeConfig
    from repro.launch.steps import plan_train
    cfg, _, _ = _smoke_pieces()
    mesh = AbstractMesh((8, 1), ("data", "model"))
    shape = ShapeConfig("t", 32, 8, "train")
    lp = plan_train(
        cfg, shape, mesh,
        merge_plan=MergePlan.parse("chip:2,host:2,pod:2:defer"),
        defer_schedule=DeferSchedule.fixed(4, ("pod",), overlap=True))
    assert lp.defer_step is not None and lp.defer_step.overlap
    assert "inflight" in lp.in_specs[0]["defer"]
    assert "inflight" in lp.in_shardings[0]["defer"]
    # the superset program for the cost walk is the land twin
    assert lp.fn is lp.defer_step.land_variants[-1]


# ---------------------------------------------------------------------------
# Slow end-to-end tests (subprocess: forced device counts)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_train_cli_merge_overlap():
    """Acceptance: the train CLI runs an overlapped :defer topology
    end-to-end, lands commits one step stale, and final-flushes the
    trailing partial cycle."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "xlstm-125m",
         "--smoke", "--steps", "5", "--batch", "8", "--seq", "32",
         "--merge-topology", "chip:2,host:2,pod:2:defer",
         "--merge-defer", "2", "--merge-overlap", "--merge-lane-parallel",
         "--ckpt-dir", "/tmp/repro_overlap_cli"],
        env=ENV, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "overlapped top-level commit" in r.stdout
    assert "final flush" in r.stdout


def test_train_cli_overlap_without_defer_rejected():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "xlstm-125m",
         "--smoke", "--steps", "1",
         "--merge-topology", "chip:2,host:2,pod:2",
         "--merge-overlap",
         "--ckpt-dir", "/tmp/repro_overlap_cli_err"],
        env=ENV, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "--merge-defer" in (r.stderr + r.stdout)


@pytest.mark.slow
def test_overlapped_train_path_equals_delayed_eager_reference():
    """End-to-end on a real 8-device mesh: the overlapped DeferredTrainStep
    (launch/land + final inflight flush) must reproduce, bit-tight, a
    reference that takes the *same* distributed eager-merged gradients
    (identical reduction order) and applies each AdamW update one step
    late. K=1 so the eager merge and the settled cascade are the same
    stage sequence — any divergence is launch/land plumbing, not float
    reassociation."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs.base import ShapeConfig, get_smoke_config
        from repro.data.pipeline import batch_at, data_config_for
        from repro.core.defer_schedule import DeferSchedule
        from repro.core.merge_plan import MergePlan
        from repro.launch.steps import lowering_rules, make_train_step
        from repro.models.module import split_params
        from repro.models.registry import build_model
        from repro.optim import make_optimizer, warmup_cosine
        from repro.sharding.partition import sharding_rules

        STEPS = 3
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
                   for i in range(STEPS)]

        class RecordOpt:
            # identity optimizer: surfaces the merged gradient via stats
            def init(self, params):
                return ()
            def step(self, params, grads, state):
                return params, state, {"grads": grads}

        def make_opt():
            return make_optimizer(cfg, warmup_cosine(3e-4, 100, 10000))

        def run_overlapped():
            opt = make_opt()
            with mesh, sharding_rules(mesh, rules):
                params, _ = split_params(model.init(jax.random.key(0)))
                step = make_train_step(
                    model, cfg, opt, 1, mesh=mesh, merge_topology=plan,
                    defer_schedule=DeferSchedule.fixed(1, ("pod",),
                                                       overlap=True))
                state = {"params": params, "opt": opt.init(params)}
                state["defer"] = step.init_defer_state(params)
                fn = step.jit()
                for b in batches:
                    state, metrics = fn(state, b)
                # the last step only launched; flush lands it
                state, fmetrics = step.flush(state)
                assert fmetrics is not None and \\
                    fmetrics.get("flushed_inflight"), fmetrics
                return jax.tree.map(np.asarray, state["params"])

        def run_reference():
            # The SAME distributed gradient computation (eager explicit
            # merge path + recording optimizer), with every AdamW update
            # applied one step late and the last one at flush time.
            opt = make_opt()
            with mesh, sharding_rules(mesh, rules):
                params, _ = split_params(model.init(jax.random.key(0)))
                rec = make_train_step(model, cfg, RecordOpt(), 1,
                                      mesh=mesh, merge_topology=eager_plan)
                rec = jax.jit(rec)
                opt_state = opt.init(params)
                queued = None
                for b in batches:
                    _, metrics = rec({"params": params, "opt": ()}, b)
                    g = metrics["grads"]
                    if queued is not None:
                        params, opt_state, _ = opt.step(params, queued,
                                                        opt_state)
                    queued = g
                params, opt_state, _ = opt.step(params, queued, opt_state)
                return jax.tree.map(np.asarray, params)

        p_ovl = run_overlapped()
        p_ref = run_reference()
        for a, b in zip(jax.tree.leaves(p_ovl), jax.tree.leaves(p_ref)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=1e-6, rtol=1e-6)
        print("OVERLAP_MATCHES_DELAYED_EAGER")
    """)
    r = subprocess.run([sys.executable, "-c", script], env=ENV,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    assert "OVERLAP_MATCHES_DELAYED_EAGER" in r.stdout


@pytest.mark.slow
def test_train_path_flush_conserves_gradient_mass():
    """N % K != 0 loses zero gradient mass: with params frozen (a summing
    no-op optimizer), the total gradient consumed by the deferred train
    path — commits plus final flush — equals the eager twin's per-cycle
    means plus the partial tail's mean, for both the serialized and the
    overlapped pipeline."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs.base import ShapeConfig, get_smoke_config
        from repro.data.pipeline import batch_at, data_config_for
        from repro.core.defer_schedule import DeferSchedule
        from repro.core.merge_plan import MergePlan
        from repro.launch.steps import lowering_rules, make_train_step
        from repro.models.module import split_params
        from repro.models.registry import build_model
        from repro.sharding.partition import sharding_rules

        K, STEPS = 2, 5  # two full cycles + a 1-step partial tail
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
                   for i in range(STEPS)]

        class SumOpt:
            # params never move -> both paths see identical gradients;
            # state accumulates every consumed (mean) gradient.
            def init(self, params):
                return jax.tree.map(jnp.zeros_like, params)
            def step(self, params, grads, state):
                return params, jax.tree.map(jnp.add, state, grads), {}

        class RecordOpt:
            def init(self, params):
                return ()
            def step(self, params, grads, state):
                return params, state, {"grads": grads}

        def mass(overlap):
            opt = SumOpt()
            with mesh, sharding_rules(mesh, rules):
                params, _ = split_params(model.init(jax.random.key(0)))
                step = make_train_step(
                    model, cfg, opt, 1, mesh=mesh, merge_topology=plan,
                    defer_schedule=DeferSchedule.fixed(K, ("pod",),
                                                       overlap=overlap))
                state = {"params": params, "opt": opt.init(params)}
                state["defer"] = step.init_defer_state(params)
                fn = step.jit()
                for b in batches:
                    state, _ = fn(state, b)
                state, fmetrics = step.flush(state)
                assert fmetrics is not None and \\
                    fmetrics.get("flushed_steps") == STEPS % K, fmetrics
                return jax.tree.map(np.asarray, state["opt"])

        def ref_mass():
            with mesh, sharding_rules(mesh, rules):
                params, _ = split_params(model.init(jax.random.key(0)))
                rec = jax.jit(make_train_step(
                    model, cfg, RecordOpt(), 1, mesh=mesh,
                    merge_topology=eager_plan))
                gs = [rec({"params": params, "opt": ()}, b)[1]["grads"]
                      for b in batches]
            total = jax.tree.map(jnp.zeros_like, params)
            for lo in range(0, STEPS, K):
                cyc = gs[lo:lo + K]
                mean = jax.tree.map(lambda *x: sum(x) / len(cyc), *cyc)
                total = jax.tree.map(jnp.add, total, mean)
            return jax.tree.map(np.asarray, total)

        want = ref_mass()
        for name, overlap in [("serialized", False), ("overlapped", True)]:
            got = mass(overlap)
            # Tolerance covers low-precision (bf16 activations/grads)
            # reassociation between the cascade's pendings and the
            # reference's host-side sums; LOST mass — a dropped step or a
            # mis-scaled cycle — would show as a 20-50% deviation.
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    atol=1e-3, rtol=0.02, err_msg=name)
        print("FLUSH_CONSERVES_GRADIENT_MASS")
    """)
    r = subprocess.run([sys.executable, "-c", script], env=ENV,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    assert "FLUSH_CONSERVES_GRADIENT_MASS" in r.stdout

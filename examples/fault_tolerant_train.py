"""Fault-tolerance demo: NaN batches, preemption, kills, elastic resume.

    PYTHONPATH=src python examples/fault_tolerant_train.py            # demo
    PYTHONPATH=src python examples/fault_tolerant_train.py --chaos    # full
    PYTHONPATH=src python examples/fault_tolerant_train.py --chaos --quick

Default mode is the classic three-phase driver demo: train through a
poisoned (NaN) batch, preempt mid-run (SIGTERM semantics — save at the
step boundary and exit), restart from the committed checkpoint.

``--chaos`` is the durability acceptance run for *deferred-commit* state
(``state["defer"]``: the pending cascade + an overlapped in-flight
launch):

1. toy integer sweep — preemption at EVERY step boundary and hard kills
   mid-cycle/mid-launch must recover bitwise-identically to the
   uninterrupted run (``repro.runtime.chaos``);
2. volatile-spec audit — the checkpoint-coverage spec (CC040) must match
   the real defer state, key for key;
3. real-model deferred train (forced 8-device host mesh, overlapped
   K=2 cascade) — kill the driver between steps, resume, and compare
   params bitwise against the uninterrupted twin;
4. elastic restore — take a mid-cycle checkpoint onto a DIFFERENT merge
   topology: outstanding mass settles into params/opt (vs. the
   flush-under-old-topology oracle) and the defer-aware LR/beta rescale
   reports the hyperparameters that keep per-data-step dynamics fixed;
5. serving tier — journal + snapshot a ShardedKV, crash it mid-epoch,
   recover onto a different shard count, and match the numpy oracle
   bitwise.
"""

import argparse
import os
import sys


def _parse_args():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chaos", action="store_true",
                   help="run the deferred-state durability acceptance suite")
    p.add_argument("--quick", action="store_true",
                   help="with --chaos: fewer kill points / smaller sweeps "
                        "(the CI configuration)")
    return p.parse_args()


ARGS = _parse_args()
if ARGS.chaos:
    # the real-model phase runs an explicit 8-way merge mesh on host CPU;
    # must be set before jax initializes its backends
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.configs.base import ShapeConfig, get_smoke_config
from repro.data.pipeline import batch_at, data_config_for
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models.module import split_params
from repro.models.registry import build_model
from repro.optim import adamw, constant
from repro.runtime import DriverConfig, TrainDriver


def demo() -> None:
    cfg = get_smoke_config("internlm2_1_8b")
    shape = ShapeConfig("ft", 32, 4, "train")
    model = build_model(cfg)
    opt = adamw(constant(1e-3))
    step_fn = jax.jit(make_train_step(model, cfg, opt, 1))
    params, _ = split_params(model.init(jax.random.key(0)))
    state0 = {"params": params, "opt": opt.init(params)}
    dcfg = data_config_for(cfg, shape, seed=0)

    def batch_fn(i):
        b = jax.tree.map(jnp.asarray, batch_at(dcfg, i))
        b["poison"] = jnp.asarray(float("nan") if i == 4 else 0.0)
        return b

    raw_step = step_fn

    def step_fn_injected(state, b):
        poison = b.pop("poison")
        new_state, metrics = raw_step(state, b)
        # injected fault: emulate a corrupt batch poisoning the loss
        metrics = dict(metrics, loss=metrics["loss"] + poison)
        return new_state, metrics

    with tempfile.TemporaryDirectory() as d:
        drv = TrainDriver(DriverConfig(ckpt_dir=d, ckpt_every=5,
                                       retry_backoff_s=0.0),
                          step_fn=step_fn_injected, batch_fn=batch_fn)

        print("phase 1: train through a poisoned batch")
        state, end = drv.run(state0, 0, 8)
        nans = [e for e in drv.events if e["event"] == "nan_rollback"]
        print(f"  reached step {end}; skipped {len(nans)} poisoned batch")

        print("phase 2: preempt mid-run (SIGTERM semantics)")
        drv2 = TrainDriver(DriverConfig(ckpt_dir=d, ckpt_every=100),
                           step_fn=step_fn_injected, batch_fn=batch_fn)
        orig = drv2.batch_fn

        def preempting(i):
            if i == end + 2:
                drv2._preempted = True
            return orig(i)
        drv2.batch_fn = preempting
        state, end2 = drv2.run(state, end, 20)
        print(f"  preempted; checkpoint committed at step "
              f"{ckpt.latest_step(d)}")

        print("phase 3: restart from the committed checkpoint")
        drv3 = TrainDriver(DriverConfig(ckpt_dir=d, ckpt_every=10),
                           step_fn=step_fn_injected, batch_fn=batch_fn)
        restored, start, _ = drv3.resume(state)
        state, end3 = drv3.run(restored, start, 5)
        losses = [e for e in drv3.events if e["event"] == "step"]
        print(f"  resumed {start} -> {end3}; "
              f"final loss {losses[-1]['loss']:.4f}")


# ---------------------------------------------------------------------------
# --chaos: deferred-state durability acceptance
# ---------------------------------------------------------------------------


def chaos_toy_sweeps(quick: bool) -> None:
    from repro.runtime import chaos

    n_steps = 5 if quick else 8
    print(f"[toy] preempt at every boundary + kills, {n_steps} steps, "
          f"2-level overlapped cascade, integer ADD")
    fac = chaos.toy_factory("chip:2,host:2:defer,pod:2:defer", (1, 2), 8,
                            width=4, overlap=True)
    with tempfile.TemporaryDirectory() as root:
        for mode in ("preempt", "kill"):
            kill_steps = ([1, 3] if quick else None)  # None = every boundary
            _, outcomes = chaos.chaos_sweep(
                fac, n_steps, os.path.join(root, mode), mode=mode,
                kill_steps=kill_steps)
            bad = [o for o in outcomes if not o.state_bitwise]
            assert not bad, f"{mode}: non-bitwise recoveries {bad}"
            print(f"  {mode}: {len(outcomes)}/{len(outcomes)} boundaries "
                  f"recovered bitwise (actions: "
                  f"{sorted({o.resume_action for o in outcomes}, key=str)})")
        # flush policy: mass conserved (params bitwise for integer ADD),
        # optimizer fold count legitimately differs
        _, outcomes = chaos.chaos_sweep(
            fac, n_steps, os.path.join(root, "flush"), mode="preempt",
            defer_save="flush", kill_steps=[1, 3])
        assert all(o.params_bitwise for o in outcomes)
        print("  flush policy: params bitwise (mass conserved), "
              "opt sequencing differs as documented")


def chaos_spec_audit() -> None:
    from repro.analysis.durability import check_step_durability
    from repro.checkpoint import tree_keys
    from repro.runtime import chaos

    step, _, state0 = chaos.toy_factory(
        "chip:2,host:2:defer,pod:2:defer", (2, 4), 8, width=4,
        overlap=True)()
    spec = step.volatile_spec(state0["params"])
    assert tree_keys(spec) == tree_keys(state0["defer"]), \
        "volatile spec drifted from the real defer state"
    assert not check_step_durability("example:toy", step, state0["params"])
    print("[spec] volatile spec == real defer state "
          f"({len(tree_keys(spec))} leaves); CC040 clean")


def chaos_real_model(quick: bool) -> None:
    from repro.core.defer_schedule import DeferSchedule
    from repro.core.merge_plan import MergePlan
    from repro.launch.steps import lowering_rules
    from repro.runtime import chaos
    from repro.sharding.partition import sharding_rules

    n_steps = 5
    kill_points = [2] if quick else [1, 2, 3, 4]
    print(f"[real] xlstm-125m, 8-way mesh, overlapped K=2 cascade; kills "
          f"at {kill_points} of {n_steps} steps")

    cfg = get_smoke_config("xlstm_125m")
    shape = ShapeConfig("t", 32, 8, "train")
    mesh = make_host_mesh(8, 1)
    rules = lowering_rules(cfg, shape, mesh)
    model = build_model(cfg)
    opt = adamw(constant(1e-3))
    plan = MergePlan.parse("chip:2,host:2,pod:2:defer", lane_parallel=True)
    sched = DeferSchedule.fixed(2, ("pod",), overlap=True)
    dcfg = data_config_for(cfg, shape, seed=0)

    def batch_fn(i):
        return jax.tree.map(jnp.asarray, batch_at(dcfg, i))

    with mesh, sharding_rules(mesh, rules):
        step = make_train_step(model, cfg, opt, 1, mesh=mesh,
                               merge_topology=plan, defer_schedule=sched)
        params, _ = split_params(model.init(jax.random.key(0)))
        state0 = {"params": params, "opt": opt.init(params),
                  "defer": step.init_defer_state(params)}
        fn = step.jit()

        # uninterrupted twin
        base = state0
        for i in range(n_steps):
            base, _ = fn(base, batch_fn(i))
        base, _ = step.flush(base)
        base_params = jax.tree.map(np.asarray, base["params"])

        for kill in kill_points:
            with tempfile.TemporaryDirectory() as d:
                dcfg_drv = DriverConfig(ckpt_dir=d, ckpt_every=1,
                                        retry_backoff_s=0.0)
                drv = TrainDriver(dcfg_drv, fn,
                                  chaos.crashing(batch_fn, kill),
                                  defer_step=step)
                try:
                    drv.run(state0, 0, n_steps)
                    raise AssertionError("crash did not fire")
                except chaos.SimulatedCrash:
                    pass
                drv2 = TrainDriver(dcfg_drv, fn, batch_fn, defer_step=step)
                state, start, report = drv2.resume(state0)
                state, _ = drv2.run(state, start, n_steps - start)
                state, _ = step.flush(state)
                got = jax.tree.map(np.asarray, state["params"])
                same = all(
                    np.array_equal(a, b) for a, b in
                    zip(jax.tree.leaves(base_params),
                        jax.tree.leaves(got)))
                assert same, f"kill@{kill}: params diverged after recovery"
                print(f"  kill@{kill}: resumed "
                      f"({report.action if report else 'fresh'} at step "
                      f"{start}) -> params BITWISE equal")


def chaos_elastic(quick: bool) -> None:
    from repro.runtime import chaos
    from repro.runtime.elastic import effective_invariants, \
        rescale_hyperparams

    print("[elastic] mid-cycle checkpoint restored onto a different "
          "topology (K=2 two-level overlap -> K=3 single-level)")
    fac_old = chaos.toy_factory("chip:2,host:2:defer,pod:2:defer", (1, 2),
                                8, width=4, overlap=True)
    fac_new = chaos.toy_factory("chip:4,pod:2:defer", (3,), 8, width=4)
    with tempfile.TemporaryDirectory() as d:
        step_o, bf_o, st_o = fac_old()
        cfg = DriverConfig(ckpt_dir=d, ckpt_every=5)
        TrainDriver(cfg, step_o, bf_o, defer_step=step_o).run(st_o, 0, 5)

        # oracle: restore under the OLD topology, flush everything
        step_v, bf_v, like_v = fac_old()
        sv, _, _ = TrainDriver(cfg, step_v, bf_v,
                               defer_step=step_v).resume(like_v)
        sv, _ = step_v.flush(sv)

        # elastic: restore under the NEW topology — outstanding mass must
        # settle into params/opt, then fresh defer state is handed out
        step_n, bf_n, like_n = fac_new()
        drv_n = TrainDriver(cfg, step_n, bf_n, defer_step=step_n)
        sn, start, report = drv_n.resume(like_n)
        assert report.action == "resolved", report
        assert np.array_equal(np.asarray(sn["params"]["w"]),
                              np.asarray(sv["params"]["w"])), \
            "elastic settle lost mass"
        assert int(sn["defer"]["t"]) == 0
        h = rescale_hyperparams(report.k_old, report.k_new, lr=1e-3)
        inv_old = effective_invariants(report.k_old, lr=1e-3)
        inv_new = effective_invariants(report.k_new, **h)
        assert np.allclose(inv_old["lr_per_step"], inv_new["lr_per_step"])
        sn, end = drv_n.run(sn, start, 3)
        print(f"  settled {report.flushed_steps} trailing step(s), "
              f"inflight={report.landed_inflight}; mass conserved bitwise; "
              f"continued {start}->{end} under K={report.k_new} with "
              f"lr'={h['lr']:.2e}, b1'={h['b1']:.4f} "
              f"(per-data-step lr invariant)")


def chaos_serving(quick: bool) -> None:
    from repro.serve import KVConfig, ShardedKV, serving_plan

    S, B, R, D, T = 4, 8, 64, 2, 12 if quick else 24
    print(f"[serve] journal+snapshot a {S}-shard KV, crash mid-epoch, "
          f"recover onto {2 * S} partitioned shards")

    def spmd(fn, *args):
        return jax.vmap(fn, axis_name="shards")(*args)

    rng = np.random.default_rng(7)
    keys = rng.integers(0, R, (T, S, B)).astype(np.int32)
    keys[:, :, -1] = -1
    vals = rng.integers(1, 9, (T, S, B, D)).astype(np.int32)
    oracle = np.zeros((R, D), np.int64)
    for t in range(T):
        m = keys[t] >= 0
        np.add.at(oracle, keys[t][m], vals[t][m])
    oracle = oracle.astype(np.int32)

    with tempfile.TemporaryDirectory() as root:
        kv = ShardedKV(KVConfig(n_keys=R, cols=D), S, spmd, commit_every=3)
        kv.attach_journal(root)
        for t in range(T // 2):
            kv.tick(keys[t], vals[t])
        kv.snapshot()
        for t in range(T // 2, T):
            kv.tick(keys[t], vals[t])
        del kv  # crash: every device buffer gone

        kv2 = ShardedKV(KVConfig(n_keys=R, cols=D, partitioned=True),
                        2 * S, spmd, plan=serving_plan(2 * S, "all"),
                        commit_every=2)
        rep = kv2.recover(root)
        kv2.flush()
        assert np.array_equal(kv2.table(), oracle), \
            "recovered table != acknowledged history"
        print(f"  snapshot@{rep['snapshot_step']}, replayed "
              f"{rep['replayed_ticks']} journaled tick(s): table BITWISE "
              f"equal to the acknowledged update stream")


def main() -> None:
    if not ARGS.chaos:
        demo()
        return
    chaos_toy_sweeps(ARGS.quick)
    chaos_spec_audit()
    chaos_elastic(ARGS.quick)
    chaos_serving(ARGS.quick)
    chaos_real_model(ARGS.quick)
    print("CHAOS_SUITE_OK")


if __name__ == "__main__":
    main()

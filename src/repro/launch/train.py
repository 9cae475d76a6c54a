"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m \
        --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/run1

On this CPU container it runs reduced shapes (--smoke uses the smoke config);
on a TPU pod the same driver runs the full mesh (``--mesh prod``). Fault
tolerance comes from runtime.TrainDriver: periodic checkpoints, SIGTERM
save-and-exit, NaN rollback + skip-batch, straggler logging. Restart the same
command and it resumes from the last committed checkpoint.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys


def _force_host_devices_for_topology() -> None:
    """A --merge-topology over N ranks needs N devices; on a CPU host (the
    smoke/dev path) force the host platform to that count BEFORE jax
    initializes, unless the caller already pinned XLA_FLAGS. Real
    accelerator backends ignore the host-platform device count."""
    if "XLA_FLAGS" in os.environ:
        return
    spec = None
    for i, a in enumerate(sys.argv):
        if a == "--merge-topology" and i + 1 < len(sys.argv):
            spec = sys.argv[i + 1]
        elif a.startswith("--merge-topology="):
            spec = a.split("=", 1)[1]
    if not spec:
        return
    # merge_plan is jax-free, so the real grammar owner can run pre-init.
    from repro.core.merge_plan import MergePlan
    try:
        n = MergePlan.parse(spec).num_ranks
    except ValueError:
        return  # malformed spec: let the in-line parse raise the clear error
    if n > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n}")


if __name__ == "__main__":
    # Only the CLI entry point may mutate XLA_FLAGS; importing this module
    # as a library must not scan argv or touch the environment.
    _force_host_devices_for_topology()

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import checkpoint as ckpt
from repro.configs.base import ShapeConfig, get_config, get_smoke_config
from repro.data.pipeline import Prefetcher, data_config_for
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import (defer_shardings, lowering_rules,
                                make_train_step, merge_axes_for,
                                train_shardings)
from repro.models.module import split_params
from repro.models.registry import build_model
from repro.optim import make_optimizer, warmup_cosine
from repro.sharding.partition import sharding_rules


def solve_defer_for_cli(merge_defer: str, cfg, shape_cfg, mesh, topology,
                        dp: int, merge_compress: bool,
                        overlap: bool = False, merge_fn=None):
    """Resolve --merge-defer into a DeferSchedule.

    ``auto`` compiles the plan's *eager twin* (defer flags stripped — so the
    deferred levels' per-step bytes are measurable), walks its HLO for the
    per-level wire vector, and solves the commit intervals against the
    step's roofline. An integer fixes every deferred level's K. With
    ``overlap`` the solver only amortizes the top level's *exposed* time
    (the launch/land pipeline hides up to a step's compute bound), and the
    schedule's commits land one step stale.
    """
    from repro.core.defer_schedule import DeferSchedule, solve_defer_schedule
    from repro.core.ccache import deferred_stages_of

    if merge_fn is None:
        from repro.core.merge_functions import ADD, int8_compressed_add
        merge_fn = int8_compressed_add() if merge_compress else ADD
    # Fail on algebra-invalid defer/overlap combinations before compiling
    # anything — the fixed-K path must be gated too, not just auto.
    if overlap:
        merge_fn.check_overlap("--merge-defer with --merge-overlap")
    else:
        merge_fn.check_deferrable("--merge-defer")

    deferred_names = tuple(
        s.name for s in deferred_stages_of(topology, dp, merge_fn=merge_fn))
    if not deferred_names:
        raise SystemExit("--merge-defer: the :defer levels all have size 1 "
                         "and compile away; drop the flags")
    if merge_defer != "auto":
        try:
            k = int(merge_defer)
        except ValueError:
            raise SystemExit(f"--merge-defer must be 'auto' or an integer, "
                             f"got {merge_defer!r}")
        if k < 1:
            raise SystemExit("--merge-defer: K must be >= 1")
        return DeferSchedule.fixed(k, deferred_names, overlap=overlap)

    from repro.launch import hlo_cost
    from repro.launch.hlo_analysis import roofline_terms
    from repro.launch.steps import plan_train

    eager = dataclasses.replace(topology, levels=tuple(
        dataclasses.replace(lv, defer=False) for lv in topology.levels))
    print("merge-defer auto: compiling the eager twin for the per-level "
          "roofline...")
    lp = plan_train(cfg, shape_cfg, mesh, merge_plan=eager,
                    merge_compress=merge_compress)
    hlo = lp.lower(mesh).compile().as_text()
    sizes = tuple(lv.size for lv in topology.levels if lv.size > 1)
    names = tuple(lv.name for lv in topology.levels if lv.size > 1)
    walk = hlo_cost.analyze_hlo(hlo, level_sizes=sizes, level_names=names)
    terms = roofline_terms(walk["flops"], walk["hbm_bytes"],
                           walk["wire_bytes"],
                           wire_bytes_by_level=walk["wire_bytes_by_level"],
                           level_names=names)
    schedule = solve_defer_schedule(
        topology, walk["wire_bytes_by_level"], names,
        compute_s=terms["compute_s"], memory_s=terms["memory_s"],
        overlap=overlap, merge_fn=merge_fn)
    return schedule


def main(argv=None) -> list[dict]:
    """Run the CLI; returns the driver's per-step events (step, loss, dt,
    grad_norm)."""
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true",
                   help="use the reduced smoke config (CPU-runnable)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--merge-group-size", type=int, default=0,
                   help="explicit hierarchical gradient merge: devices per "
                        "intra-group level on the data axis (0 = implicit "
                        "XLA reduction); two-level shorthand for "
                        "--merge-topology")
    p.add_argument("--merge-topology", default="",
                   help="N-level MergePlan over the data-parallel axes, "
                        "innermost level first: 'chip:4,host:16,pod:2' "
                        "(level flags: :compress :software; the product of "
                        "sizes must equal the data-parallel device count; "
                        ":defer levels additionally need --merge-defer)")
    p.add_argument("--merge-defer", default="",
                   help="commit schedule for the topology's :defer levels: "
                        "'auto' solves per-level intervals K from the "
                        "compiled step's per-level roofline (commit a level "
                        "when its amortized wire time stops dominating); an "
                        "integer fixes K for every deferred level. The "
                        "optimizer steps once per full commit on the "
                        "cycle's mean gradient (K-step gradient "
                        "accumulation)")
    p.add_argument("--merge-overlap", action="store_true",
                   help="overlap the deferred top-level commit with the "
                        "next step's compute: the full-commit step launches "
                        "the exchange and it lands one step later (the "
                        "optimizer steps one step stale on the cycle's mean "
                        "gradient). Requires --merge-defer; only valid for "
                        "additive gradient merges")
    p.add_argument("--merge-lane-parallel", action="store_true",
                   help="shard the representative role over each unit's "
                        "lanes so upper-level exchanges bandwidth-"
                        "parallelize (requires --merge-topology)")
    p.add_argument("--merge-compress", action="store_true",
                   help="int8-compress the outermost-level gradient "
                        "exchange (requires --merge-group-size or "
                        "--merge-topology)")
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--mesh", choices=["host", "prod"], default="host")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log", default=None)
    args = p.parse_args(argv)
    compile_cache.enable()

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    shape_cfg = ShapeConfig("cli", args.seq, args.batch, "train")
    model = build_model(cfg)

    mesh = (make_production_mesh() if args.mesh == "prod"
            else make_host_mesh(data=jax.device_count(), model=1))
    rules = lowering_rules(cfg, shape_cfg, mesh)

    optimizer = make_optimizer(
        cfg, warmup_cosine(args.lr, args.warmup, args.steps))
    topology = None
    if args.merge_group_size and args.merge_topology:
        raise SystemExit("--merge-group-size and --merge-topology are "
                         "mutually exclusive")
    if args.merge_compress and not (args.merge_group_size
                                    or args.merge_topology):
        raise SystemExit("--merge-compress requires --merge-group-size or "
                         "--merge-topology")
    if args.merge_lane_parallel and not args.merge_topology:
        raise SystemExit("--merge-lane-parallel requires --merge-topology")
    if args.merge_group_size:
        from repro.core.ccache import MergeTopology
        dp = mesh.shape.get("data", 1)
        if dp % args.merge_group_size != 0:
            raise SystemExit(
                f"--merge-group-size {args.merge_group_size} does not divide "
                f"the data axis ({dp} devices)")
        topology = MergeTopology(group_size=args.merge_group_size,
                                 axis_name="data")
    elif args.merge_topology:
        from repro.core.merge_plan import MergePlan
        try:
            topology = MergePlan.parse(
                args.merge_topology,
                lane_parallel=args.merge_lane_parallel)
        except ValueError as e:
            raise SystemExit(f"--merge-topology: {e}")
        axes = merge_axes_for(mesh, topology)
        dp = 1
        for a in (axes if isinstance(axes, tuple) else (axes,)):
            dp *= mesh.shape.get(a, 1)
        try:
            topology.validate(dp)
        except ValueError as e:
            raise SystemExit(f"--merge-topology: {e} "
                             f"(data-parallel axes {axes})")
        if args.batch % dp != 0:
            raise SystemExit(
                f"--batch {args.batch} must be divisible by the merge "
                f"topology's {dp} ranks (each rank takes an equal batch "
                f"shard)")

    defer_schedule = None
    has_deferred = topology is not None and getattr(topology, "has_deferred",
                                                    False)
    if args.merge_defer and not has_deferred:
        raise SystemExit("--merge-defer requires a --merge-topology with "
                         ":defer levels")
    if args.merge_overlap and not args.merge_defer:
        raise SystemExit("--merge-overlap requires --merge-defer (the "
                         "launch/land pipeline splits a *deferred* commit "
                         "across two steps)")
    if has_deferred:
        if not args.merge_defer:
            raise SystemExit(
                "--merge-topology has :defer levels; pass --merge-defer "
                "auto|K to schedule the commits (the optimizer steps once "
                "per commit on the K-step mean gradient), or drop the "
                ":defer flags for an eager merge every step")
        defer_schedule = solve_defer_for_cli(
            args.merge_defer, cfg, shape_cfg, mesh, topology, dp,
            args.merge_compress, overlap=args.merge_overlap)
        print("merge-defer schedule:", defer_schedule.describe())
        if (args.steps % defer_schedule.period) != 0:
            print(f"note: --steps {args.steps} is not a multiple of the "
                  f"commit period {defer_schedule.period}; the trailing "
                  f"partial cycle is settled by the final flush")
    step_fn = make_train_step(model, cfg, optimizer, args.microbatches,
                              mesh=mesh, merge_topology=topology,
                              merge_compress=args.merge_compress,
                              defer_schedule=defer_schedule)

    with mesh, sharding_rules(mesh, rules):
        params, param_axes = split_params(
            model.init(jax.random.key(args.seed)))
        state = {"params": params, "opt": optimizer.init(params)}
        # state lives on the mesh by the logical rules and each batch is
        # split over the data axes, so every device takes its share
        state_sh, batch_sh = train_shardings(model, shape_cfg, mesh, rules,
                                             param_axes, params, state["opt"])
        if defer_schedule is not None:
            state["defer"] = step_fn.init_defer_state(params)
            state_sh["defer"] = defer_shardings(
                state["defer"], mesh, merge_axes_for(mesh, topology))
        shardings = dict(in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, NamedSharding(mesh, P())))
        jitted = (step_fn.jit(**shardings) if defer_schedule is not None
                  else jax.jit(step_fn, **shardings))

        # Resume from the last committed checkpoint if present.
        start = 0
        last = ckpt.latest_step(args.ckpt_dir)
        if last is not None:
            state, extras = ckpt.restore(args.ckpt_dir, state)
            start = extras.get("next_step", last)
            print(f"resumed from checkpoint step {last} -> start {start}")
        state = jax.device_put(state, state_sh)

        dcfg = data_config_for(cfg, shape_cfg, seed=args.seed)
        prefetch = Prefetcher(dcfg, start_step=start)

        from repro.runtime import DriverConfig, TrainDriver
        driver = TrainDriver(
            DriverConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                         log_path=args.log),
            step_fn=lambda s, b: jitted(s, b),
            batch_fn=lambda i: jax.device_put(prefetch.get()[1], batch_sh),
            # deferred runs record the durability manifest next to each
            # boundary save so a restore under a changed plan/schedule can
            # settle the pendings (docs/fault_tolerance.md)
            defer_step=(step_fn if defer_schedule is not None else None))
        try:
            state, end = driver.run(state, start, args.steps - start)
        finally:
            prefetch.stop()
        if defer_schedule is not None:
            # Drain the deferred machinery: land any in-flight overlapped
            # commit and settle the trailing partial cycle, so no gradient
            # mass is dropped on the floor at end of run.
            state, fmetrics = step_fn.flush(state)
            if fmetrics is not None:
                parts = []
                if fmetrics.get("flushed_inflight"):
                    parts.append("landed the in-flight commit")
                if "flushed_steps" in fmetrics:
                    parts.append(f"settled a {fmetrics['flushed_steps']}-step"
                                 f" partial cycle")
                print("final flush:", ", ".join(parts))
        losses = [e for e in driver.events if e.get("event") == "step"]
        if losses:
            print(f"steps {start}..{end}: loss {losses[0]['loss']:.4f} -> "
                  f"{losses[-1]['loss']:.4f}")
        return losses


if __name__ == "__main__":
    main()

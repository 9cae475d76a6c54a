"""Sharded commutative KV serving driver.

    PYTHONPATH=src python -m repro.launch.kv_serve --shards 8 \
        --keys 65536 --ticks 64 --batch 512 --dist pareto --defer 8

Runs the :mod:`repro.serve` tier on a real device mesh: on a CPU host the
CLI forces ``--xla_force_host_platform_device_count=<shards>`` before jax
initializes (accelerator backends ignore the host-platform count), so the
same command exercises an 8-way shard_map locally and a real pod in
production.

``--defer`` picks the commit policy:

* ``sync`` — the fully-synchronized reference (merge every tick).
* an integer ``K`` — fixed commit interval over a fully deferred plan.
* ``auto`` — walk the compiled sync tick's HLO for the per-level wire
  vector, hand it to ``solve_defer_schedule`` with the measured tick
  time, and serve with the solved schedule (printed before the run).
* ``adaptive`` — same roofline inputs, but the commit interval re-solves
  online from the measured ingest rate (``AdaptiveDeferSchedule``).

``--partitioned`` home-shards the settled table over the mesh (each row
lives on exactly one shard; reads route by ``key % shards``) and bounds
pending state with ring/spill buffers; ``--overlap`` additionally
pipelines the commit's launch/land halves (requires ``--partitioned``).

``--merge`` picks the update: ``add`` counts (every value 1), ``xor``
serves HPCC RandomAccess (a 64-bit word is ``--cols 2`` int32 lanes, each
update XORs a random word in), checked against numpy's
``bitwise_xor.at``. XOR is neither scalable nor idempotent, so it cannot
``--overlap``:

    PYTHONPATH=src python -m repro.launch.kv_serve --shards 4 \
        --merge xor --cols 2 --partitioned --dist uniform
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def _parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--keys", type=int, default=1 << 16,
                   help="table rows (counter keys)")
    p.add_argument("--cols", type=int, default=4,
                   help="columns per key")
    p.add_argument("--shards", type=int, default=None,
                   help="mesh width (default: every device)")
    p.add_argument("--ticks", type=int, default=64,
                   help="update batches to ingest")
    p.add_argument("--batch", type=int, default=512,
                   help="updates per shard per tick")
    p.add_argument("--defer", default="8",
                   help="sync | auto | adaptive | K (fixed commit "
                        "interval)")
    p.add_argument("--partitioned", action="store_true",
                   help="home-shard the settled table over the mesh "
                        "(routed reads, ring/spill pendings)")
    p.add_argument("--overlap", action="store_true",
                   help="overlap the commit's launch/land halves "
                        "(requires --partitioned)")
    p.add_argument("--spill-blocks", type=int, default=64,
                   help="blocked engine, partitioned: spill buffer slots")
    p.add_argument("--merge", default="add", choices=["add", "xor"],
                   help="the update: add counts, xor HPCC's word ^= value")
    p.add_argument("--consistency", default="eventual",
                   choices=["eventual", "read_your_writes"])
    p.add_argument("--engine", default="kernel",
                   choices=["kernel", "blocked"])
    p.add_argument("--dist", default="pareto",
                   choices=["uniform", "pareto"],
                   help="simulated user key distribution")
    p.add_argument("--users", type=int, default=1 << 20,
                   help="simulated user population")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--ways", type=int, default=8,
                   help="blocked engine: cache ways")
    return p.parse_args(argv)


def _force_host_devices() -> None:
    """Pin the host platform to --shards devices BEFORE jax initializes,
    unless the caller already set XLA_FLAGS (same discipline as
    launch.train: only the CLI entry point touches the environment)."""
    if "XLA_FLAGS" in os.environ:
        return
    n = None
    for i, a in enumerate(sys.argv):
        if a == "--shards" and i + 1 < len(sys.argv):
            n = a = sys.argv[i + 1]
        elif a.startswith("--shards="):
            n = a.split("=", 1)[1]
    try:
        n = int(n) if n is not None else 8
    except ValueError:
        return  # malformed: let argparse raise the clear error
    if n > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n}")


if __name__ == "__main__":
    _force_host_devices()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main(argv=None) -> None:
    args = _parse_args(argv)
    from repro.launch import compile_cache
    compile_cache.enable()
    from repro.apps.sharded import build_mesh, mesh_spmd
    from repro.core.defer_schedule import (AdaptiveDeferSchedule,
                                           DeferSchedule,
                                           solve_defer_schedule)
    from repro.core.merge_functions import default_registry
    from repro.launch import hlo_cost
    from repro.serve import KVConfig, ShardedKV, serving_plan

    S = args.shards or jax.device_count()
    R, D, B = args.keys, args.cols, args.batch
    axis = "shards"
    spmd = mesh_spmd(build_mesh(S, axis), axis)

    cfg = KVConfig(n_keys=R, cols=D, dtype=jnp.int32,
                   merge=default_registry()[args.merge],
                   consistency=args.consistency, engine=args.engine,
                   ways=args.ways, partitioned=args.partitioned,
                   spill_blocks=args.spill_blocks)
    sync_mode = args.defer == "sync"
    if args.partitioned and sync_mode:
        raise SystemExit("--partitioned needs deferred commits; pick "
                         "--defer K|auto|adaptive")
    if args.overlap and not args.partitioned:
        raise SystemExit("--overlap pipelines the partitioned store's "
                         "commit; add --partitioned")
    if args.partitioned and R % S:
        raise SystemExit(f"--partitioned needs --keys divisible by "
                         f"--shards (got {R} % {S} = {R % S})")
    plan = serving_plan(S, "none" if sync_mode else "all")

    schedule = commit_every = None
    if args.defer in ("auto", "adaptive"):
        # Walk the sync tick's compiled HLO for the wire vector, measure
        # one deferred non-commit tick, and solve the schedule. Both
        # probes run the replicated store: the partitioned ring is sized
        # by max_period, which the never-committing timer would blow up.
        probe_cfg = KVConfig(n_keys=R, cols=D, dtype=jnp.int32,
                             consistency=args.consistency,
                             engine=args.engine, ways=args.ways)
        probe = ShardedKV(probe_cfg, S, spmd, plan=serving_plan(S, "none"))
        sizes = tuple(lv.size for lv in plan.levels)
        names = tuple(lv.name for lv in plan.levels)
        group = 1
        for sz in sizes[:-1]:
            group *= sz

        hlo = spmd.lower(probe.raw_tick_fn(),
                         jax.ShapeDtypeStruct((S, R, D), jnp.int32),
                         jax.ShapeDtypeStruct((S, B), jnp.int32),
                         jax.ShapeDtypeStruct((S, B, D), jnp.int32)
                         ).compile().as_text()
        walk = hlo_cost.analyze_hlo(hlo, intra_group_size=group,
                                    level_sizes=sizes, level_names=names)
        k0 = np.zeros((S, B), np.int32)
        v0 = np.ones((S, B, D), np.int32)
        timer = ShardedKV(probe_cfg, S, spmd, plan=plan,
                          commit_every=1 << 20)  # never commits in probe
        timer.tick(k0, v0)  # compile
        t0 = time.perf_counter()
        for _ in range(4):
            timer.tick(k0, v0)
        jax.block_until_ready(timer.settled)
        tick_s = (time.perf_counter() - t0) / 4
        wire = walk["wire_bytes_by_level_total"]
        if args.defer == "adaptive":
            # Charge the measured tick entirely to per-update work so the
            # schedule responds to the observed ingest rate; a full batch
            # reproduces the probe's compute bound.
            schedule = AdaptiveDeferSchedule(
                plan, wire, names, per_update_s=tick_s / (S * B),
                overlap=args.overlap, merge_fn=cfg.merge)
        else:
            schedule = solve_defer_schedule(
                plan, wire, names, compute_s=tick_s,
                overlap=args.overlap, merge_fn=cfg.merge)
            if args.partitioned:
                # The partitioned store commits all deferred levels in
                # one launch; collapse the nested solution to its period.
                schedule = DeferSchedule(
                    level_names=schedule.level_names,
                    intervals=(schedule.period,)
                    * len(schedule.level_names),
                    predicted=schedule.predicted, overlap=args.overlap)
        print("solved schedule:")
        print(schedule.describe())
    elif not sync_mode:
        try:
            commit_every = int(args.defer)
        except ValueError:
            raise SystemExit(f"--defer must be sync|auto|adaptive|K, "
                             f"got {args.defer!r}")
        if args.overlap:
            from repro.core.merge_plan import compile_plan
            deferred = tuple(s.name for s in compile_plan(
                plan, S, merge_fn=cfg.merge) if s.defer)
            schedule = DeferSchedule.fixed(commit_every, deferred,
                                           overlap=True)
            commit_every = None

    kv = ShardedKV(cfg, S, spmd, plan=plan, schedule=schedule,
                   commit_every=commit_every)

    try:
        # repo-root import (python -m from the checkout puts cwd on path)
        from benchmarks.traces import key_stream
    except ImportError:
        def key_stream(n, n_keys, dist, n_users, seed):
            rng = np.random.default_rng(seed)
            if dist == "uniform":
                users = rng.integers(0, n_users, n)
            else:
                ranks = (rng.pareto(1.05, n) * n_users / 20).astype(np.int64)
                users = np.minimum(ranks, n_users - 1)
            return ((users * 2654435761) % n_keys).astype(np.int32)
    keys = key_stream(args.ticks * S * B, R, args.dist,
                      n_users=args.users, seed=args.seed
                      ).reshape(args.ticks, S, B)
    if args.merge == "xor":
        vals = np.random.default_rng(args.seed + 1).integers(
            -2**31, 2**31, (args.ticks, S, B, D)).astype(np.int32)
    else:
        vals = np.ones((args.ticks, S, B, D), np.int32)

    kv.tick(keys[0], vals[0])  # compile
    jax.block_until_ready(kv.settled)
    t0 = time.perf_counter()
    for t in range(1, args.ticks):
        kv.tick(keys[t], vals[t])
    jax.block_until_ready(kv.settled)
    wall = time.perf_counter() - t0
    ups = S * B * (args.ticks - 1) / wall

    kv.flush()
    tbl = kv.table()
    print(f"{args.dist} stream: {args.ticks} ticks x {S} shards x {B} "
          f"updates, merge={args.merge}, defer={args.defer}, "
          f"engine={args.engine}")
    print(f"ingest: {wall:.3f}s  ({ups:,.0f} updates/s, "
          f"{ups / 1e9:.6f} GUPS)")
    if args.merge == "xor":
        ref = np.zeros((R, D), np.int32)
        np.bitwise_xor.at(ref, keys.reshape(-1), vals.reshape(-1, D))
        print(f"table equals numpy bitwise_xor.at: "
              f"{bool(np.array_equal(tbl, ref))}")
    else:
        total = int(tbl[:, 0].astype(np.int64).sum())
        print(f"settled mass col0: {total} "
              f"(= {S * B * args.ticks} updates ingested)")
    for k, v in kv.counters().items():
        if k != "schedule":
            print(f"  {k}: {v}")


if __name__ == "__main__":
    main()

"""GUPS: the serving tier vs the fully-synchronized reference.

The HPCC RandomAccess question asked of ``serve.kv``: how many commutative
updates/sec can the 8-shard store ingest, privatized-deferred vs the
lock-array strawman's coherence discipline (merge every batch)?  Three
measurements per run, all tagged ``@repro-bench`` records:

* **throughput** — wall-clock GUPS over uniform and Pareto-skewed key
  streams from a simulated ``2^20``-user population
  (``benchmarks.traces.key_stream``).  Both stores run the same scatter
  phase; the only difference is the reconciliation bill: the sync store
  pays the full hierarchical exchange every tick, the privatized store
  pays one elementwise coalesce per tick plus the cascade once per K.
  The gated claim: privatized >= 2x sync GUPS on the skewed trace.
* **correctness** — after ``flush()`` the privatized table must equal the
  sync store AND a numpy oracle bitwise (integer ADD is exact), so the
  speedup is measured over the *same* eventual state, not a cheaper one.
* **wire** — per-level byte vectors (``hlo_cost``) of the compiled sync
  tick / deferred non-commit tick / commit tick.  A fully deferred plan's
  non-commit tick must move ZERO collective bytes, and the K-cycle
  amortized top-level bytes must undercut the sync tick's by >= K/2
  (``check_level_costs.py`` gates both).  The measured vector also feeds
  ``solve_defer_schedule`` for an informational auto-K record.

The partitioned store (``KVConfig(partitioned=True)``) gets its own record
family: ``kv_part_bitwise`` (same eventual state), ``pareto_part*`` GUPS,
``kv_part_footprint`` (per-device resident bytes, replicated vs
home-sharded — the gated >= 4x drop), ``kv_part_step/commit/launch/land``
wire vectors (non-commit must be zero-collective; the overlapped halves
must match ``ccache.overlap_program_manifest``), and ``kv_part_adaptive``
(the load-driven K).

Respawns under ``--xla_force_host_platform_device_count=8`` like the
other mesh studies; the parent process keeps its single-device view.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

# Fixed commit interval for the gated runs: deterministic amortization
# (the solved schedule is emitted as its own informational record).
COMMIT_EVERY = 8
N_SHARDS = 8


def bench_kv_gups(quick: bool = False) -> list[dict]:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={N_SHARDS}",
               PYTHONPATH=os.pathsep.join(
                   [os.path.abspath("src"), os.path.abspath("."),
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.kv_gups", "--sub",
         "quick" if quick else "full"],
        env=env, capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        return [{"bench": "kv_gups", "error": out.stderr[-600:]}]
    from benchmarks.records import iter_records
    return list(iter_records(out.stdout.splitlines()))


def _sub_main(quick: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.records import emit_record
    from benchmarks.traces import key_stream
    from repro.apps.sharded import build_mesh, mesh_spmd
    from repro.core.defer_schedule import solve_defer_schedule
    from repro.launch import hlo_cost
    from repro.serve.kv import KVConfig, ShardedKV, serving_plan

    S, K = N_SHARDS, COMMIT_EVERY
    # Big-table regime: the reconciliation bill (per-level exchanges of
    # R*D*4 bytes per device) must dominate the O(B) scatter, as it does
    # at production scale — small tables measure dispatch overhead.
    R = 1 << 20                 # table rows (counters)
    D = 4                       # columns per key
    B = 1024                    # updates per shard per tick
    warm_cycles, timed_cycles = (1, 2) if quick else (1, 4)
    n_users = 1 << 20
    axis = "shards"

    spmd = mesh_spmd(build_mesh(S, axis), axis)
    # both stores use the same scatter (the backend picks it); the
    # contest is the merge bill.
    cfg = KVConfig(n_keys=R, cols=D, dtype=jnp.int32)
    plan_sync = serving_plan(S, "none")
    plan_priv = serving_plan(S, "all")
    sync = ShardedKV(cfg, S, spmd, plan=plan_sync)
    priv = ShardedKV(cfg, S, spmd, plan=plan_priv, commit_every=K)

    def batches(dist: str, ticks: int, seed: int):
        ks = key_stream(ticks * S * B, R, dist, n_users=n_users, seed=seed)
        keys = ks.reshape(ticks, S, B)
        vals = np.ones((ticks, S, B, D), np.int32)
        return jnp.asarray(keys), jnp.asarray(vals)

    # ---- correctness: same eventual state, bitwise ----------------------
    t_corr = K + 3              # exercises commit ticks and a partial cycle
    keys, vals = batches("pareto", t_corr, seed=7)
    ref = np.zeros((R, D), np.int64)
    np.add.at(ref, np.asarray(keys).reshape(-1), 1)
    for t in range(t_corr):
        sync.tick(keys[t], vals[t])
        priv.tick(keys[t], vals[t])
    priv.flush()
    sync_tbl = sync.table().astype(np.int64)
    priv_tbl = priv.table().astype(np.int64)
    match = bool(np.array_equal(sync_tbl, priv_tbl)
                 and np.array_equal(sync_tbl, ref))
    emit_record({"bench": "kv_gups", "case": f"bitwise_s{S}",
                 "n_shards": S, "commit_every": K, "ticks": t_corr,
                 "match": match,
                 "max_abs_err": int(np.abs(sync_tbl - priv_tbl).max())})

    # ---- throughput -----------------------------------------------------
    def timed(store, keys, vals, warm: int, ticks: int) -> float:
        for t in range(warm):
            store.tick(keys[t], vals[t])
        jax.block_until_ready(store.settled)
        t0 = time.perf_counter()
        for t in range(warm, warm + ticks):
            store.tick(keys[t], vals[t])
        jax.block_until_ready(store.settled)
        return time.perf_counter() - t0

    speedups = {}
    for dist in ("uniform", "pareto"):
        warm, ticks = warm_cycles * K, timed_cycles * K
        keys, vals = batches(dist, warm + ticks, seed=11)
        rates = {}
        for label, store in (("sync", sync), ("priv", priv)):
            wall = timed(store, keys, vals, warm, ticks)
            ups = S * B * ticks / wall
            rates[label] = ups
            emit_record({"bench": "kv_gups",
                         "case": f"{dist}_{label}_s{S}",
                         "n_shards": S, "dist": dist, "n_keys": R,
                         "cols": D, "batch_per_shard": B,
                         "ticks": ticks, "n_users": n_users,
                         "commit_every": K if label == "priv" else 1,
                         "wall_s": round(wall, 4),
                         "updates_per_s": round(ups, 1),
                         "gups": round(ups / 1e9, 6)})
        speedups[dist] = rates["priv"] / rates["sync"]
        emit_record({"bench": "kv_gups", "case": f"{dist}_speedup_s{S}",
                     "n_shards": S, "dist": dist, "commit_every": K,
                     "gups_speedup_x": round(speedups[dist], 3)})

    # ---- per-level wire vectors of the compiled tick programs -----------
    sizes = tuple(lv.size for lv in plan_sync.levels)
    names = tuple(lv.name for lv in plan_sync.levels)
    group = 1
    for sz in sizes[:-1]:
        group *= sz

    def _walk(fn, *args):
        hlo = spmd.lower(fn, *args).compile().as_text()
        return hlo_cost.analyze_hlo(hlo, intra_group_size=group,
                                    level_sizes=sizes, level_names=names)

    tbl_s = jax.ShapeDtypeStruct((S, R, D), jnp.int32)
    pend_s = tuple(tbl_s for _ in range(priv.n_deferred))
    keys_s = jax.ShapeDtypeStruct((S, B), jnp.int32)
    vals_s = jax.ShapeDtypeStruct((S, B, D), jnp.int32)

    w_sync = _walk(sync.raw_tick_fn(), tbl_s, keys_s, vals_s)
    w_step = _walk(priv.raw_tick_fn(0), tbl_s, pend_s, keys_s, vals_s)
    w_commit = _walk(priv.raw_tick_fn(priv.n_deferred),
                     tbl_s, pend_s, keys_s, vals_s)

    def _emit_wire(case, walk, extra=None):
        emit_record({"bench": "kv_gups", "case": f"{case}_s{S}",
                     "n_shards": S, "level_names": list(names),
                     "level_sizes": list(sizes),
                     "wire_bytes_by_level_total":
                         walk["wire_bytes_by_level_total"],
                     "collectives": {c: v["count"] for c, v in
                                     walk["per_collective"].items()},
                     **(extra or {})})

    _emit_wire("kv_sync_tick", w_sync)
    _emit_wire("kv_defer_step", w_step)
    _emit_wire("kv_defer_commit", w_commit, {"commit_every": K})

    # amortized per-tick bytes of a K-cycle vs the sync tick's top level
    step_lv = w_step["wire_bytes_by_level_total"]
    commit_lv = w_commit["wire_bytes_by_level_total"]
    amort = [(s * (K - 1) + c) / K for s, c in zip(step_lv, commit_lv)]
    sync_top = w_sync["wire_bytes_by_level_total"][-1]
    emit_record({
        "bench": "kv_gups", "case": f"kv_defer_amortized_s{S}",
        "n_shards": S, "commit_every": K, "level_names": list(names),
        "wire_bytes_by_level_total": amort,
        "top_level_bytes_sync": sync_top,
        "top_level_bytes_amortized": amort[-1],
        "top_level_amortization_x": round(sync_top / amort[-1], 2)
        if amort[-1] else None})

    # informational: the roofline-solved schedule from the measured wire
    # vector and the measured non-commit tick time
    keys, vals = batches("pareto", 4, seed=13)
    t0 = time.perf_counter()
    for t in range(4):
        priv.tick(keys[t], vals[t])
    jax.block_until_ready(priv.settled)
    tick_s = (time.perf_counter() - t0) / 4
    sched = solve_defer_schedule(plan_priv,
                                 w_sync["wire_bytes_by_level_total"],
                                 names, compute_s=tick_s, merge_fn=cfg.merge)
    emit_record({"bench": "kv_gups", "case": f"kv_defer_auto_s{S}",
                 "n_shards": S, "measured_tick_s": round(tick_s, 6),
                 **sched.as_dict()})

    # ---- the partitioned store: footprint, throughput, wire -------------
    # Home-sharded settled rows + ring pendings: per-device resident state
    # drops from (1 + n_deferred) * R * D to R * D / S + the ring, at the
    # same (or better) GUPS — the commit bill is identical, the non-commit
    # tick gets cheaper (an O(B) append instead of a table-wide scatter).
    from repro.core.defer_schedule import (AdaptiveDeferSchedule,
                                           DeferSchedule)
    pcfg = KVConfig(n_keys=R, cols=D, dtype=jnp.int32, partitioned=True)
    part = ShardedKV(pcfg, S, spmd, plan=plan_priv, commit_every=K)
    part_ov = ShardedKV(pcfg, S, spmd, plan=plan_priv,
                        schedule=DeferSchedule.fixed(
                            K, part._deferred_names, overlap=True))

    keys, vals = batches("pareto", t_corr, seed=7)
    for t in range(t_corr):
        part.tick(keys[t], vals[t])
        part_ov.tick(keys[t], vals[t])
    part.flush()
    part_ov.flush()
    emit_record({"bench": "kv_gups", "case": f"kv_part_bitwise_s{S}",
                 "n_shards": S, "commit_every": K, "ticks": t_corr,
                 "match": bool(
                     np.array_equal(part.table().astype(np.int64), ref)),
                 "match_overlap": bool(
                     np.array_equal(part_ov.table().astype(np.int64), ref))})

    part_rates = {}
    for label, store in (("part", part), ("part_overlap", part_ov)):
        warm, ticks = warm_cycles * K, timed_cycles * K
        keys, vals = batches("pareto", warm + ticks, seed=11)
        wall = timed(store, keys, vals, warm, ticks)
        ups = S * B * ticks / wall
        part_rates[label] = ups
        emit_record({"bench": "kv_gups", "case": f"pareto_{label}_s{S}",
                     "n_shards": S, "dist": "pareto", "n_keys": R,
                     "cols": D, "batch_per_shard": B, "ticks": ticks,
                     "n_users": n_users, "commit_every": K,
                     "partitioned": True, "overlap": "overlap" in label,
                     "wall_s": round(wall, 4),
                     "updates_per_s": round(ups, 1),
                     "gups": round(ups / 1e9, 6)})
    emit_record({"bench": "kv_gups", "case": f"pareto_part_speedup_s{S}",
                 "n_shards": S, "dist": "pareto", "commit_every": K,
                 "partitioned": True,
                 "gups_speedup_x": round(part_rates["part"]
                                         / rates["sync"], 3)})

    # per-device resident footprint: the tentpole's memory claim (the
    # gated record uses the NON-overlapped store — an in-flight launched
    # aggregate is a transient dense table during its 1-tick window)
    repl_bytes = priv.resident_state_bytes()
    part_bytes = part.resident_state_bytes()
    emit_record({"bench": "kv_gups", "case": f"kv_part_footprint_s{S}",
                 "n_shards": S, "commit_every": K, "n_keys": R, "cols": D,
                 "resident_bytes_replicated": repl_bytes,
                 "resident_bytes_partitioned": part_bytes,
                 "resident_drop_x": round(repl_bytes / part_bytes, 2),
                 "gups_vs_sync_x": round(part_rates["part"]
                                         / rates["sync"], 3)})

    # wire: the partitioned non-commit tick must move zero collective
    # bytes (CC020); the commit and the overlapped launch/land halves
    # must match their scheduled manifests (CC021, scripts/lint_plans.py)
    def _batched(specs):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((S,) + s.shape, s.dtype), specs)

    p_specs = _batched(part.tick_arg_specs(B))
    w_pstep = _walk(part.raw_tick_fn(0), *p_specs)
    w_pcommit = _walk(part.raw_tick_fn(part.n_deferred), *p_specs)
    po_specs = _batched(part_ov.tick_arg_specs(B))
    po_land = _batched(part_ov.tick_arg_specs(B, land=True))
    w_launch = _walk(part_ov.raw_tick_fn(part_ov.n_deferred), *po_specs)
    w_land = _walk(part_ov.raw_tick_fn(0, land=True), *po_land)
    _emit_wire("kv_part_step", w_pstep, {"partitioned": True})
    _emit_wire("kv_part_commit", w_pcommit,
               {"partitioned": True, "commit_every": K})
    _emit_wire("kv_part_launch", w_launch,
               {"partitioned": True, "overlap": True, "half": "launch",
                "commit_every": K})
    _emit_wire("kv_part_land", w_land,
               {"partitioned": True, "overlap": True, "half": "land",
                "commit_every": K})

    # informational: the adaptive schedule's K at the measured ingest rate
    ad = AdaptiveDeferSchedule(plan_priv,
                               w_sync["wire_bytes_by_level_total"], names,
                               base_compute_s=0.0,
                               per_update_s=tick_s / (S * B),
                               k_max=max(K, 2), merge_fn=cfg.merge)
    k_idle = ad.period
    ad.observe(S * B)
    for _ in range(ad.period):
        ad.due_count(0)
    emit_record({"bench": "kv_gups", "case": f"kv_part_adaptive_s{S}",
                 "n_shards": S, "k_idle": k_idle, "k_loaded": ad.period,
                 **ad.as_dict()})

    # blocked-engine counters: the faithful merge-on-evict model on a
    # short skewed stream (Fig. 9's events at serving granularity)
    bcfg = KVConfig(n_keys=1 << 10, cols=D, dtype=jnp.int32,
                    engine="blocked", ways=8, block_rows=8)
    bkv = ShardedKV(bcfg, S, spmd, plan=serving_plan(S, "all"),
                    commit_every=K)
    bk = key_stream(K * S * 64, 1 << 10, "pareto", n_users=n_users,
                    seed=3).reshape(K, S, 64)
    bv = np.ones((K, S, 64, D), np.int32)
    for t in range(K):
        bkv.tick(bk[t], bv[t])
    bkv.flush()
    c = bkv.counters()
    emit_record({"bench": "kv_gups", "case": f"blocked_counters_s{S}",
                 "n_shards": S, "ways": bcfg.ways,
                 "block_rows": bcfg.block_rows, "ticks": K,
                 "evict_merges": c["evict_merges"],
                 "silent_evicts": c["silent_evicts"],
                 "flush_merges": c["flush_merges"],
                 "total_merges": c["total_merges"]})


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--sub", choices=["quick", "full"])
    ap.add_argument("--quick", action="store_true")
    a = ap.parse_args()
    if a.sub:
        _sub_main(a.sub == "quick")
    else:
        from benchmarks.records import emit_record
        for r in bench_kv_gups(quick=a.quick):
            emit_record(r)

"""Paper figures/tables from the simulator: Fig 6, Fig 7, Fig 8, Fig 9,
Table 3 — plus the mesh-scaling companion study (``bench_apps_sharded``)
that runs the same apps (BFS / PageRank / k-means) as *real* sharded
MergePlan programs on a forced host mesh instead of the trace simulator.

The companion study reports, per mesh size:

* correctness vs the single-device reference for both the all-eager plan
  and the deferred/overlapped commit schedule (BFS must match bitwise —
  MIN is a lattice join; PageRank/k-means to float tolerance);
* per-level wire vectors (``hlo_cost.analyze_hlo`` over the compiled
  superstep programs) for the eager superstep, the deferred non-commit
  superstep, and the K-cycle commit — and the amortized per-superstep
  top-level bytes, which must show the ~K-fold reduction the ``:defer``
  plan promises (``check_level_costs.py`` gates this).

Each simulator runner prints CSV rows and returns them as dicts; the
sharded study emits tagged ``@repro-bench`` records from its subprocess."""

from __future__ import annotations

import time

from benchmarks.simulator import MachineConfig, run_trace
from benchmarks.traces import APPS, kmeans

# Working-set sweep relative to the (scaled) LLC; the paper runs 25%-400%.
# 4.0 is included only with --full-size traces (simulation time).
FRACS = (0.25, 0.5, 1.0, 2.0)


def _run(mc: MachineConfig, app: str, version: str, frac: float,
         **kw) -> tuple[dict, dict]:
    builder, _ = APPS[app]
    trace, meta = builder(mc, version, frac, **kw)
    t0 = time.time()
    res = run_trace(mc, trace)
    res["wall_s"] = time.time() - t0
    return res, meta


def fig6_speedup(mc: MachineConfig, quick: bool = False) -> list[dict]:
    """Per-app speedup of DUP and CCache relative to FGL vs. working set."""
    rows = []
    fracs = (0.5, 2.0) if quick else FRACS
    for app, (_, versions) in APPS.items():
        for frac in fracs:
            base = None
            for version in versions:
                res, meta = _run(mc, app, version, frac)
                if version == "fgl":
                    base = res["cycles_max"]
                speedup = base / max(res["cycles_max"], 1)
                rows.append({
                    "figure": "fig6", "app": app, "version": version,
                    "llc_frac": frac, "cycles": res["cycles_max"],
                    "speedup_vs_fgl": round(speedup, 3),
                    "llc_miss": res["llc_miss"],
                    "invalidations": res["invalidations"],
                    "evict_merges": res["evict_merges"],
                    "flush_merges": res["flush_merges"],
                })
    return rows


def fig7_half_llc(mc: MachineConfig, quick: bool = False) -> list[dict]:
    """CCache with HALF the LLC vs. DUP with the full LLC, equal absolute
    working set (= the full-size LLC capacity)."""
    rows = []
    half = MachineConfig(scale=mc.scale * 2)
    for app in APPS:
        if quick and app not in ("kv_store", "bfs"):
            continue
        dup_version = "dup"
        res_d, _ = _run(mc, app, dup_version, 1.0)
        # same absolute working set on the halved machine = 2x its LLC
        res_c, _ = _run(half, app, "ccache", 2.0)
        rows.append({
            "figure": "fig7", "app": app,
            "dup_cycles_fullLLC": res_d["cycles_max"],
            "ccache_cycles_halfLLC": res_c["cycles_max"],
            "ccache_speedup_with_half_llc":
                round(res_d["cycles_max"] / max(res_c["cycles_max"], 1), 3),
        })
    return rows


def table3_memory(mc: MachineConfig) -> list[dict]:
    """Peak memory overhead of FGL/DUP normalized to CCache (analytic from
    the trace layouts)."""
    rows = []
    for app, (builder, versions) in APPS.items():
        foot = {}
        for version in versions:
            _, meta = builder(mc, version, 1.0)
            foot[version] = meta["footprint_lines"]
        base = foot["ccache"]
        rows.append({"figure": "table3", "app": app,
                     **{f"{v}_over_ccache": round(foot[v] / base, 2)
                        for v in foot}})
    return rows


def fig8_characterization(mc: MachineConfig, quick: bool = False
                          ) -> list[dict]:
    """Invalidations / LLC misses / directory accesses per 1k cycles."""
    rows = []
    fracs = (1.0,) if quick else (0.5, 2.0)
    for app, (_, versions) in APPS.items():
        for frac in fracs:
            for version in versions:
                res, _ = _run(mc, app, version, frac)
                kcyc = max(res["cycles_max"], 1) / 1000
                rows.append({
                    "figure": "fig8", "app": app, "version": version,
                    "llc_frac": frac,
                    "inval_per_kcyc": round(res["invalidations"] / kcyc, 3),
                    "llc_miss_per_kcyc": round(res["llc_miss"] / kcyc, 3),
                    "directory_per_kcyc": round(res["directory"] / kcyc, 3),
                })
    return rows


def fig9_merge_on_evict(mc: MachineConfig) -> list[dict]:
    """Merge-count reduction from merge-on-evict (vs. eager merging) and the
    dirty-merge silent-eviction count (PageRank's 24x fewer merges)."""
    rows = []
    # K-means: eager merges after every point vs. merge-on-evict.
    for version in ("ccache", "ccache_eager"):
        trace, _ = kmeans(mc, version, 1.0)
        res = run_trace(mc, trace)
        rows.append({"figure": "fig9", "app": "kmeans", "version": version,
                     "total_merges": res["evict_merges"] + res["flush_merges"],
                     "evict_merges": res["evict_merges"],
                     "flush_merges": res["flush_merges"],
                     "silent_evicts": res["silent_evicts"]})
    eager = rows[-1]["total_merges"]
    opt = rows[-2]["total_merges"]
    rows.append({"figure": "fig9", "app": "kmeans",
                 "version": "reduction",
                 "merge_reduction_x": round(eager / max(opt, 1), 1)})
    # PageRank dirty-merge: silent evictions = merges avoided on clean CData.
    res, _ = _run(mc, "pagerank", "ccache", 1.0)
    merges = res["evict_merges"] + res["flush_merges"]
    rows.append({"figure": "fig9", "app": "pagerank", "version": "ccache",
                 "total_merges": merges,
                 "silent_evicts": res["silent_evicts"],
                 "dirty_merge_reduction_x":
                     round((merges + res["silent_evicts"]) / max(merges, 1), 2)})
    return rows


# Deferred commit interval for the sharded apps study; matches the apps'
# acceptance runs and the kmeans commit schedule.
APPS_DEFER_K = 4


def bench_apps_sharded(quick: bool = False) -> list[dict]:
    """Mesh-scaling companion to fig 6: the apps as sharded MergePlan
    programs. Respawns in a forced-device subprocess (like hierarchy/lm_tier)
    so the parent keeps its single-device view; ``--quick`` runs the 8-shard
    mesh only, full adds 16 shards."""
    import os
    import subprocess
    import sys
    n_dev = 8 if quick else 16
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}",
               PYTHONPATH=os.pathsep.join(
                   [os.path.abspath("src"), os.path.abspath("."),
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.paper_apps", "--sub-apps",
         "quick" if quick else "full"],
        env=env, capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        return [{"bench": "apps_sharded", "error": out.stderr[-600:]}]
    from benchmarks.records import iter_records
    return list(iter_records(out.stdout.splitlines()))


def _apps_sub_main(quick: bool) -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks.records import emit_record
    from repro.apps import bfs_superstep, pagerank_superstep
    from repro.apps.common import default_plan
    from repro.apps.sharded import build_mesh, mesh_spmd, run_app
    from repro.core import ccache
    from repro.core.merge_functions import ADD, MIN
    from repro.launch import hlo_cost

    k = APPS_DEFER_K
    n_vertices = 24 if quick else 48
    n_edges = 96 if quick else 160
    alpha = 0.5
    base = (1.0 - alpha) / n_vertices

    for n_shards in ((8,) if quick else (8, 16)):
        # --- correctness on the real mesh, Pallas scatter phase ---
        for app in ("bfs", "pagerank", "kmeans"):
            rec = run_app(app, n_shards, defer_k=k, use_pallas=True,
                          n_vertices=n_vertices, n_edges=n_edges)
            emit_record({"bench": "apps_sharded",
                         "case": f"{app}_correctness_s{n_shards}", **rec})

        # --- per-level wire vectors of the compiled superstep programs ---
        axis = "shards"
        spmd = mesh_spmd(build_mesh(n_shards, axis), axis)
        plan = default_plan(n_shards)
        plan_d = default_plan(n_shards, defer_top=True)
        sizes = tuple(lv.size for lv in plan.levels)
        names = tuple(lv.name for lv in plan.levels)
        group = 1
        for s in sizes[:-1]:
            group *= s

        dist_s = jax.ShapeDtypeStruct((n_shards, n_vertices), jnp.int32)
        rank_s = jax.ShapeDtypeStruct((n_shards, n_vertices), jnp.float32)
        e_per = -(-(n_edges + n_vertices) // n_shards)
        edge_s = jax.ShapeDtypeStruct((n_shards, e_per), jnp.int32)

        def _walk(fn, *args):
            hlo = spmd.lower(fn, *args).compile().as_text()
            return hlo_cost.analyze_hlo(hlo, intra_group_size=group,
                                        level_sizes=sizes, level_names=names)

        def _emit(app, case, walk, extra=None):
            row = {"bench": "apps_sharded", "app": app,
                   "case": f"{app}_{case}_s{n_shards}", "n_shards": n_shards,
                   "level_names": list(names), "level_sizes": list(sizes),
                   "wire_bytes_by_level_total":
                       walk["wire_bytes_by_level_total"],
                   "collectives": {c: v["count"]
                                   for c, v in walk["per_collective"].items()}}
            row.update(extra or {})
            emit_record(row)
            return row

        def _amortized(app, eager_w, step_w, commit_w):
            """Per-superstep bytes of a K-cycle: K-1 non-commit steps + one
            commit step, vs the all-eager superstep's top level."""
            step_lv = step_w["wire_bytes_by_level_total"]
            commit_lv = commit_w["wire_bytes_by_level_total"]
            amort = [(s * (k - 1) + c) / k
                     for s, c in zip(step_lv, commit_lv)]
            eager_top = eager_w["wire_bytes_by_level_total"][-1]
            emit_record({
                "bench": "apps_sharded", "app": app,
                "case": f"{app}_defer_amortized_s{n_shards}",
                "n_shards": n_shards, "commit_every": k,
                "level_names": list(names),
                "wire_bytes_by_level_total": amort,
                "top_level_bytes_eager": eager_top,
                "top_level_bytes_amortized": amort[-1],
                "top_level_amortization_x": round(eager_top / amort[-1], 2)
                if amort[-1] else None})

        # BFS: eager superstep merges all levels; deferred superstep joins
        # the eager scope only; the commit settles the pod-scope pending.
        def bfs_eager(dist, src, dst):
            cand = bfs_superstep(dist, src, dst)
            return jnp.minimum(
                dist, ccache.hierarchical_merge(cand, axis, MIN, plan))

        def bfs_defer_step(dist, src, dst, pending):
            cand = bfs_superstep(dist, src, dst)
            u = ccache.partial_merge(cand, axis, MIN, plan_d)
            return jnp.minimum(dist, u), jnp.minimum(pending, u)

        def bfs_defer_commit(dist, src, dst, pending):
            cand = bfs_superstep(dist, src, dst)
            u = ccache.partial_merge(cand, axis, MIN, plan_d)
            settled = ccache.settle_deferred(
                jnp.minimum(pending, u), axis, MIN, plan_d)
            return (jnp.minimum(jnp.minimum(dist, u), settled),
                    jnp.full_like(pending, jnp.iinfo(jnp.int32).max))

        bw_e = _walk(bfs_eager, dist_s, edge_s, edge_s)
        bw_s = _walk(bfs_defer_step, dist_s, edge_s, edge_s, dist_s)
        bw_c = _walk(bfs_defer_commit, dist_s, edge_s, edge_s, dist_s)
        _emit("bfs", "eager_step", bw_e)
        _emit("bfs", "defer_step", bw_s)
        _emit("bfs", "defer_commit", bw_c, {"commit_every": k})
        _amortized("bfs", bw_e, bw_s, bw_c)

        # PageRank: same three programs over the ADD merge.
        def pr_eager(r, src, dst, deg):
            c = pagerank_superstep(r, src, dst, deg, alpha=alpha)
            return base + ccache.hierarchical_merge(c, axis, ADD, plan)

        def pr_defer_step(r, remote, src, dst, deg):
            c = pagerank_superstep(r, src, dst, deg, alpha=alpha)
            u = ccache.partial_merge(c, axis, ADD, plan_d)
            return base + u + remote, remote

        def pr_defer_commit(r, remote, src, dst, deg):
            c = pagerank_superstep(r, src, dst, deg, alpha=alpha)
            u = ccache.partial_merge(c, axis, ADD, plan_d)
            full = ccache.settle_deferred(u, axis, ADD, plan_d)
            return base + full, full - u

        pw_e = _walk(pr_eager, rank_s, edge_s, edge_s, rank_s)
        pw_s = _walk(pr_defer_step, rank_s, rank_s, edge_s, edge_s, rank_s)
        pw_c = _walk(pr_defer_commit, rank_s, rank_s, edge_s, edge_s, rank_s)
        _emit("pagerank", "eager_step", pw_e)
        _emit("pagerank", "defer_step", pw_s)
        _emit("pagerank", "defer_commit", pw_c, {"commit_every": k})
        _amortized("pagerank", pw_e, pw_s, pw_c)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--sub-apps", choices=["quick", "full"])
    ap.add_argument("--quick", action="store_true")
    a = ap.parse_args()
    if a.sub_apps:
        _apps_sub_main(a.sub_apps == "quick")
    else:
        from benchmarks.records import emit_record
        for r in bench_apps_sharded(quick=a.quick):
            emit_record(r)

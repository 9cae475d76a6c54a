"""Flat vs hierarchical merge on the 2-pod mesh: wire bytes + simulated time.

Compiles (never executes — the collectives are what we're costing) each merge
strategy under ``shard_map`` over a flattened data-parallel axis shaped like
the production pod mesh, then walks the partitioned HLO with
``hlo_cost.analyze_hlo(level_sizes=...)`` to split collective bytes into the
per-level hierarchy vector (chip / host / pod). Simulated time charges each
level at its bandwidth:

    t = chip / (chips * ICI_BW) + host / (chips * ICI_BW/2) + pod / DCI_TOTAL

where DCI_TOTAL is the shared inter-pod pipe. Claims under test:

* two-level (PR-1): the representative-only inter-group exchange cuts
  inter-pod bytes by the group-size factor vs the flat butterfly;
* three-level MergePlan (chip:16,host:16,pod:2 on the full mesh): the same
  per-level, with the top level ≥100x cheaper than the flat butterfly's,
  and the lane-parallel exchange moving identical bytes over stride-times
  more links;
* merge-on-evict: a plan with ``pod:...:defer`` pays the pod level once per
  K-step commit — the per-step amortized top-level bytes drop ~K-fold
  (paper's mergeable bit, level 2);
* overlapped commits (hier3_overlap): the launch/land pipeline puts the
  top-level commit exchange in the same program as the next step's compute
  (no data dependency), hiding >= 50% of its measured time behind a
  compute-bound step — and the overlap-aware solver picks K no larger than
  the serialized solver's.

Device counts: full = pod2x16x16 (512 forced host devices, chip:16,host:16,
pod:2); ``--quick`` = pod2x4x4 (32 devices, chip:4,host:4,pod:2). Like
lm_tier, the multi-device part respawns in a subprocess so the parent keeps
its single-device view.
"""

from __future__ import annotations

import os
import subprocess
import sys

# Modeled hardware (mirrors repro.launch.hlo_analysis; DCI_TOTAL is the
# aggregate inter-pod pipe rather than a per-chip share). DCI_CONGESTED is
# the oversubscribed pipe the auto-defer canary solves against — the regime
# where deferring the top level matters.
ICI_BW = 50e9
HOST_BW = 25e9
DCI_TOTAL = 800e9
DCI_CONGESTED = DCI_TOTAL / 128
DEFER_K = 8
PEAK_FLOPS = 197e12  # per-chip bf16 rate (mirrors hlo_analysis.PEAK_FLOPS)


def bench_hierarchy(quick: bool = False) -> list[dict]:
    """Run the flat-vs-hierarchical comparison in a forced-device subprocess."""
    n_dev = 32 if quick else 512
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}",
               PYTHONPATH=os.pathsep.join(
                   [os.path.abspath("src"), os.path.abspath("."),
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.hierarchy", "--sub",
         "quick" if quick else "full"],
        env=env, capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        return [{"bench": "hierarchy", "error": out.stderr[-600:]}]
    from benchmarks.records import iter_records
    return list(iter_records(out.stdout.splitlines()))


def _sim_time_s(by_level_total: list[float], chips: int) -> float:
    bws = [chips * ICI_BW, chips * HOST_BW, DCI_TOTAL]
    return sum(b / bw for b, bw in zip(by_level_total, bws))


def _sub_main(quick: bool) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from benchmarks.records import emit_record
    from repro.core import ccache
    from repro.core import merge_functions as mf
    from repro.core.defer_schedule import solve_defer_schedule
    from repro.core.merge_plan import MergePlan
    from repro.launch import hlo_cost

    # pod2x4x4 (quick) or pod2x16x16: the dp axis flattens (pod, data, model)
    # rank-major, so one pod = the first `group` ranks — aligned groups, and
    # the 3-level plan nests chip blocks inside host blocks inside pods.
    chips = 32 if quick else 512
    group = chips // 2
    chip = 4 if quick else 16
    host = group // chip
    mesh_name = "pod2x4x4" if quick else "pod2x16x16"
    level_sizes = (chip, host, 2)
    level_names = ("chip", "host", "pod")
    mesh = jax.make_mesh((chips,), ("dp",))
    n = (1 << 16) if quick else (1 << 20)  # per-device f32 update elements
    sds = jax.ShapeDtypeStruct((chips, n), jnp.float32)
    topo = ccache.MergeTopology(group_size=group)
    spec3 = f"chip:{chip},host:{host},pod:2"
    plan3 = MergePlan.parse(spec3)
    plan3_lane = MergePlan.parse(spec3, lane_parallel=True)
    plan3_defer = MergePlan.parse(spec3.replace("pod:2", "pod:2:defer"),
                                  lane_parallel=True)

    def _walk(fn, in_specs=P("dp"), args=(sds,)):
        f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                  out_specs=P("dp"), check_vma=False))
        hlo = f.lower(*args).compile().as_text()
        return hlo_cost.analyze_hlo(hlo, intra_group_size=group,
                                    level_sizes=level_sizes,
                                    level_names=level_names)

    def _emit(case: str, walk: dict, extra: dict | None = None) -> dict:
        by_level = walk["wire_bytes_by_level_total"]
        row = {
            "bench": "hierarchy", "mesh": mesh_name, "chips": chips,
            "group_size": group, "case": case,
            "level_names": list(level_names),
            "level_sizes": list(level_sizes),
            "update_mb_per_device": round(n * 4 / 1e6, 2),
            "wire_bytes_per_device": walk["wire_bytes"],
            "wire_bytes_by_level_total": by_level,
            "wire_bytes_intra_total": walk["wire_bytes_intra_total"],
            "wire_bytes_inter_total": walk["wire_bytes_inter_total"],
            "sim_time_us": round(_sim_time_s(by_level, chips) * 1e6, 2),
            "collectives": {k: v["count"]
                            for k, v in walk["per_collective"].items()}}
        row.update(extra or {})
        emit_record(row)
        return row

    cases = {
        "flat_butterfly": lambda u: ccache.tree_merge(u, "dp", mf.ADD),
        "hierarchical": lambda u: ccache.hierarchical_merge(
            u, "dp", mf.ADD, topo),
        "hierarchical_softpath": lambda u: ccache.hierarchical_merge(
            u, "dp", mf.ADD, topo, force_tree=True),
        "hierarchical_int8_inter": lambda u: ccache.hierarchical_merge(
            u, "dp", mf.int8_compressed_add(), topo, compress=True),
        "hier3_rep": lambda u: ccache.hierarchical_merge(
            u, "dp", mf.ADD, plan3),
        "hier3_lane": lambda u: ccache.hierarchical_merge(
            u, "dp", mf.ADD, plan3_lane),
        "psum_fastpath": lambda u: ccache.reduce_update(u, "dp", mf.ADD),
    }
    rows = {}
    for name, fn in cases.items():
        rows[name] = _emit(name, _walk(fn))

    # Merge-on-evict at pod scope: the per-step eager levels (chip+host)
    # vs the deferred pod-level commit paid once every K steps.
    step_walk = _walk(lambda u: ccache.partial_merge(u, "dp", mf.ADD,
                                                     plan3_defer))
    commit_walk = _walk(
        lambda u, m: ccache.commit_deferred(
            ccache.PendingUpdate(update=u), m, "dp", mf.ADD, plan3_defer),
        in_specs=(P("dp"), P("dp")), args=(sds, sds))
    rows["hier3_defer_step"] = _emit("hier3_defer_step", step_walk)
    rows["hier3_defer_commit"] = _emit("hier3_defer_commit", commit_walk)
    step_lv = step_walk["wire_bytes_by_level_total"]
    commit_lv = commit_walk["wire_bytes_by_level_total"]
    amortized = [s + c / DEFER_K for s, c in zip(step_lv, commit_lv)]
    eager_top = rows["hier3_lane"]["wire_bytes_by_level_total"][-1]
    emit_record({
        "bench": "hierarchy", "mesh": mesh_name, "chips": chips,
        "case": "hier3_defer_amortized", "commit_every": DEFER_K,
        "level_names": list(level_names),
        "wire_bytes_by_level_total": amortized,
        "sim_time_us": round(_sim_time_s(amortized, chips) * 1e6, 2),
        "top_level_bytes_eager": eager_top,
        "top_level_bytes_amortized": amortized[-1],
        "top_level_amortization_x": round(
            eager_top / amortized[-1], 2) if amortized[-1] else None})

    # Schedule-aware defer: the roofline solver picks K from the measured
    # eager per-level vector under a DCI oversubscribed vs the benchmark's
    # aggregate pipe (the regime where merge-on-evict matters), and the
    # measured amortization at that K must realize the prediction — the CI
    # canary for the solver + engine + classifier pipeline.
    lane_lv = rows["hier3_lane"]["wire_bytes_by_level_total"]
    schedule = solve_defer_schedule(
        plan3_defer, lane_lv, level_names,
        bandwidths=[chips * ICI_BW, chips * HOST_BW, DCI_CONGESTED])
    k_auto = schedule.intervals[-1]
    amort_auto = [s + c / k_auto for s, c in zip(step_lv, commit_lv)]
    predicted_top = schedule.predicted["per_level"][-1][
        "amortized_bytes_per_step"]
    emit_record({
        "bench": "hierarchy", "mesh": mesh_name, "chips": chips,
        "case": "hier3_defer_auto", "commit_every": k_auto,
        "schedule": schedule.as_dict(),
        "level_names": list(level_names),
        "wire_bytes_by_level_total": amort_auto,
        "sim_time_us": round(_sim_time_s(amort_auto, chips) * 1e6, 2),
        "top_level_bytes_eager": lane_lv[-1],
        "top_level_bytes_predicted": predicted_top,
        "top_level_bytes_measured": amort_auto[-1],
        "predicted_amortization_x": round(lane_lv[-1] / predicted_top, 2)
        if predicted_top else None,
        "top_level_amortization_x": round(lane_lv[-1] / amort_auto[-1], 2)
        if amort_auto[-1] else None})

    # Overlapped deferred commits (launch/land): the land-step program
    # carries the launched cycle's top-level exchange NEXT TO the next
    # step's compute, with no data dependency between them — so the
    # scheduler can hide the exchange behind the compute. Both sides are
    # measured from one compiled program's HLO (wire bytes for the
    # exchange, dot flops for the compute) and charged at the modeled
    # rates; the hidden fraction is what the overlap saves per commit
    # versus the serialized ``:defer`` commit. The matmul chain stands in
    # for a training step's fwd/bwd, sized to ~2/3 of the top-level
    # exchange time: the overlap hides most (but not all) of the commit,
    # and the overlap-aware solver — which only amortizes the exposed
    # remainder — picks a smaller K than the serialized solver at the
    # same compute bound.
    mm, chain = (1024, 5) if quick else (3072, 3)
    wsds = jax.ShapeDtypeStruct((chips, mm, mm), jnp.float32)

    def overlap_land(u, w):
        y = w[0]
        for _ in range(chain):
            y = y @ y
        settled = ccache.settle_inflight(u, "dp", mf.ADD, plan3_defer)
        return settled, y[None]

    f = jax.jit(jax.shard_map(overlap_land, mesh=mesh,
                              in_specs=(P("dp"), P("dp")),
                              out_specs=(P("dp"), P("dp")), check_vma=False))
    ovl_hlo = f.lower(sds, wsds).compile().as_text()
    ovl_walk = hlo_cost.analyze_hlo(ovl_hlo, intra_group_size=group,
                                    level_sizes=level_sizes,
                                    level_names=level_names)
    t_top_s = ovl_walk["wire_bytes_by_level_total"][-1] / DCI_CONGESTED
    t_comp_s = ovl_walk["flops"] / PEAK_FLOPS
    hidden_s = min(t_top_s, t_comp_s)
    exposed_s = t_top_s - hidden_s
    # Apples-to-apples solver comparison at this step's compute bound:
    # overlap amortizes only the exposed remainder, so its K is never
    # larger (and usually smaller — committing more often is free while
    # the exchange stays behind the compute).
    bws = [chips * ICI_BW, chips * HOST_BW, DCI_CONGESTED]
    sched_serial = solve_defer_schedule(plan3_defer, lane_lv, level_names,
                                        bandwidths=bws, compute_s=t_comp_s)
    sched_ovl = solve_defer_schedule(plan3_defer, lane_lv, level_names,
                                     bandwidths=bws, compute_s=t_comp_s,
                                     overlap=True)
    emit_record({
        "bench": "hierarchy", "mesh": mesh_name, "chips": chips,
        "case": "hier3_overlap",
        "level_names": list(level_names),
        "wire_bytes_by_level_total": ovl_walk["wire_bytes_by_level_total"],
        "top_exchange_bytes": ovl_walk["wire_bytes_by_level_total"][-1],
        "top_exchange_time_us": round(t_top_s * 1e6, 2),
        "overlap_compute_time_us": round(t_comp_s * 1e6, 2),
        "exposed_time_us": round(exposed_s * 1e6, 2),
        "hidden_frac": round(hidden_s / t_top_s, 4) if t_top_s else None,
        "k_serialized": sched_serial.intervals[-1],
        "k_overlap": sched_ovl.intervals[-1],
        "collectives": {k: v["count"]
                        for k, v in ovl_walk["per_collective"].items()}})


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
        for r in bench_hierarchy(quick=a.quick):
            emit_record(r)

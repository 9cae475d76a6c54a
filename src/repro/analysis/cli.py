"""`python -m repro.analysis` — the static MergePlan verifier's sweep CLI.

Four sweeps, one report (``scripts/lint_plans.py`` is the thin wrapper
``scripts/ci.sh`` runs before the benchmark gates):

* **merges** — every merge fn the repo ships (``standard_merges``) through
  the trait certifier (CC00x);
* **configs** — every arch in ``src/repro/configs/`` audited against the
  production mesh geometries ``launch/dryrun.py`` lowers on (single- and
  multi-pod), eager and defer-top, with the plain and the compressed
  gradient merge (CC013/CC014);
* **apps** — the paper apps' scatter supersteps traced with the merge axis
  bound and asserted collective-free (CC010), plus their plan audits;
* **serve** — the ``ShardedKV`` serving plans on a forced 8-way host mesh
  (one subprocess, ``kv_gups``-style): jaxpr privatization lint of the
  hot path (CC010/CC011/CC012), compiled-HLO walks of every tick program
  against ``ccache.program_manifest`` (CC020/CC021), and donation/aliasing
  checks (CC022).

``--fixtures`` runs the seeded-violation suite instead: each known-bad
input must trip its stable CC code (the linter's own canary; the pytest
twin is ``tests/test_analysis.py``). ``--suppress CODE[@SITE]`` keeps a
finding visible but non-fatal; ``--json PATH`` writes the machine-readable
report. See docs/static_analysis.md for the code catalog.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
from typing import Callable, Optional

from repro.analysis.diagnostics import Diagnostic, Report

_SUB_TAG = "@repro-lint"
_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
_SERVE_SHARDS = 8


def _log(msg: str) -> None:
    print(f"lint_plans: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# in-process sweeps
# ---------------------------------------------------------------------------


def sweep_merges(report: Report) -> None:
    """CC00x: certify every shipped merge fn's declared traits."""
    from repro.analysis.traits import certify_merge_fn
    from repro.core.merge_functions import standard_merges

    for fn in standard_merges():
        site = f"merge:{fn.name}"
        report.mark_checked(site)
        report.extend(certify_merge_fn(fn, site=site))


def _production_plans():
    """The merge-plan geometries ``launch/dryrun.py`` lowers every config
    on: per mesh, the all-eager plan and the defer-top what-if."""
    from repro.core.merge_plan import MergeLevel, MergePlan

    out = []
    for multi_pod in (False, True):
        sizes = (16, 16) + ((2,) if multi_pod else ())
        names = ("chip", "host") + (("pod",) if multi_pod else ())
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
        eager = MergePlan(levels=tuple(
            MergeLevel(nm, sz) for nm, sz in zip(names, sizes)))
        defer_top = MergePlan(levels=tuple(
            MergeLevel(nm, sz, defer=(i == len(sizes) - 1))
            for i, (nm, sz) in enumerate(zip(names, sizes))))
        out.append((mesh_name, sizes, (("eager", eager),
                                       ("defer_top", defer_top))))
    return out


def sweep_configs(report: Report) -> None:
    """CC013/CC014: audit every config's production plan geometries with
    the gradient merges the train step actually routes through them."""
    from repro.analysis.jaxpr import audit_plan
    from repro.configs.base import ARCH_IDS
    from repro.core.merge_functions import ADD, int8_compressed_add

    merges = (ADD, int8_compressed_add())
    plans = _production_plans()
    for arch in ARCH_IDS:
        for mesh_name, sizes, variants in plans:
            axis_size = 1
            for s in sizes:
                axis_size *= s
            for kind, plan in variants:
                for m in merges:
                    site = f"config:{arch}:{mesh_name}:{kind}:{m.name}"
                    report.mark_checked(site)
                    report.extend(audit_plan(plan, axis_size, merge_fn=m,
                                             site=site))


def sweep_apps(report: Report, axis_name: str = "shards",
               axis_size: int = 8) -> None:
    """CC010 on the paper apps' scatter supersteps (privatized phases must
    trace collective-free) + CC013/CC014 on their default plan."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr import audit_plan, check_noncommit_region
    from repro.apps.bfs import bfs_superstep
    from repro.apps.common import default_plan
    from repro.apps.kmeans import kmeans_step
    from repro.apps.pagerank import pagerank_superstep
    from repro.core.merge_functions import ADD, MIN

    n, e, k, d = 64, 128, 4, 3
    S = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    cases = [
        ("app:bfs.superstep", bfs_superstep,
         (S((n,), i32), S((e,), i32), S((e,), i32))),
        ("app:pagerank.superstep",
         functools.partial(pagerank_superstep, alpha=0.85),
         (S((n,), f32), S((e,), i32), S((e,), i32), S((n,), f32))),
        ("app:kmeans.step", kmeans_step,
         (S((e, d), f32), S((k, d), f32))),
    ]
    for site, fn, avals in cases:
        report.mark_checked(site)
        report.extend(check_noncommit_region(fn, axis_name, axis_size,
                                             avals, site))
    plan = default_plan(axis_size)
    for m in (ADD, MIN):
        site = f"app:default_plan[{axis_size}]:{m.name}"
        report.mark_checked(site)
        report.extend(audit_plan(plan, axis_size, merge_fn=m, site=site))


# ---------------------------------------------------------------------------
# serve sweep: forced host mesh in a subprocess (XLA_FLAGS must be set
# before jax imports — same respawn pattern as benchmarks/kv_gups.py)
# ---------------------------------------------------------------------------


def sweep_serve(report: Report, timeout: int = 1800) -> None:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=("--xla_force_host_platform_device_count="
                          f"{_SERVE_SHARDS}"),
               PYTHONPATH=os.pathsep.join(
                   [_SRC, os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--sub", "serve"],
        env=env, capture_output=True, text=True, timeout=timeout)
    done = False
    for line in out.stdout.splitlines():
        if not line.startswith(_SUB_TAG):
            continue
        obj = json.loads(line[len(_SUB_TAG):])
        if "checked" in obj:
            report.mark_checked(obj["checked"])
        elif "diag" in obj:
            report.add(Diagnostic(**obj["diag"]))
        elif obj.get("done"):
            done = True
    if out.returncode != 0 or not done:
        raise RuntimeError(
            f"serve sweep subprocess failed (rc={out.returncode}):\n"
            f"{out.stderr[-2000:]}\n{out.stdout[-1000:]}")


def _sub_serve() -> None:
    """Child half of :func:`sweep_serve`; emits tagged JSON on stdout."""
    import jax
    import jax.numpy as jnp

    from repro.analysis import placement
    from repro.analysis.jaxpr import (audit_plan, check_kv_tick_taint,
                                      check_noncommit_region)
    from repro.apps.sharded import build_mesh, mesh_spmd
    from repro.launch import hlo_cost
    from repro.serve.kv import KVConfig, ShardedKV, serving_plan

    def emit(obj: dict) -> None:
        print(f"{_SUB_TAG} {json.dumps(obj)}", flush=True)

    def emit_diags(diags) -> None:
        for d in diags:
            emit({"diag": d.as_dict()})

    S = _SERVE_SHARDS
    axis = "shards"
    spmd = mesh_spmd(build_mesh(S, axis), axis)
    on_cpu = jax.default_backend() == "cpu"
    R, D, B = 256, 2, 32
    cfg = KVConfig(n_keys=R, cols=D, dtype=jnp.int32)
    # All serving plans share one level geometry; the walk cost model
    # needs only sizes/names, which are defer-invariant.
    base_plan = serving_plan(S, "all")
    sizes = tuple(lv.size for lv in base_plan.levels)
    names = tuple(lv.name for lv in base_plan.levels)

    def batched(specs):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((S,) + s.shape, s.dtype), specs)

    def walk(fn, specs, donate=()):
        hlo = spmd.lower(fn, *batched(specs),
                         donate=donate).compile().as_text()
        return hlo, hlo_cost.analyze_hlo(hlo, level_sizes=sizes,
                                         level_names=names)

    for defer in ("all", "top", "none"):
        plan = serving_plan(S, defer)
        store = ShardedKV(cfg, S, spmd, plan=plan,
                          **({} if defer == "none" else {"commit_every": 4}))
        site = f"kv[{defer}]"
        specs = store.tick_arg_specs(B)

        # plan/trait audit (CC013/CC014)
        emit({"checked": f"{site}:plan"})
        emit_diags(audit_plan(plan, S, merge_fn=cfg.merge,
                              site=f"{site}:plan"))

        # jaxpr privatization lint of the fully deferred hot path
        if defer == "all":
            tick0 = store.raw_tick_fn(0)
            emit({"checked": f"{site}:jaxpr[due=0]"})
            emit_diags(check_noncommit_region(
                tick0, axis, S, specs, f"{site}:jaxpr[due=0]"))
            settled_s, pendings_s, keys_s, vals_s = specs
            emit_diags(check_kv_tick_taint(
                tick0, axis, S, settled_s, pendings_s, keys_s, vals_s,
                f"{site}:jaxpr[due=0]"))

        # HLO placement lint: every tick program vs its scheduled manifest
        for due in store.supported_dues:
            prog_site = f"{site}:tick[due={due}]"
            emit({"checked": prog_site})
            fn = (store.raw_tick_fn() if due == "sync"
                  else store.raw_tick_fn(due))
            _, w = walk(fn, specs)
            manifest = (store.scheduled_manifest() if due == "sync"
                        else store.scheduled_manifest(due))
            emit_diags(placement.check_commit_walk(w, manifest, prog_site))

        # donation lint: the full-commit tick with the driver's donations
        don_site = f"{site}:donation"
        emit({"checked": don_site})
        fn = (store.raw_tick_fn() if store.synchronized
              else store.raw_tick_fn(store.n_deferred))
        hlo, _ = walk(fn, specs, donate=store.donate_argnums)
        expected = placement.donated_param_numbers(batched(specs),
                                                   store.donate_argnums)
        emit_diags(placement.check_donation(hlo, expected, don_site,
                                            require=not on_cpu))

    # partitioned stores: home-sharded settled rows, launch/land halves.
    # The tick signature differs from the replicated kernel store (ring /
    # cache+spill pendings), so the kv-taint unpack does not apply; the
    # noncommit region lint and the manifest/donation walks do.
    from repro.core.defer_schedule import DeferSchedule

    plan = serving_plan(S, "all")
    pcfg = KVConfig(n_keys=R, cols=D, dtype=jnp.int32, partitioned=True)
    pstore = ShardedKV(pcfg, S, spmd, plan=plan, commit_every=4)
    ostore = ShardedKV(pcfg, S, spmd, plan=plan,
                       schedule=DeferSchedule.fixed(
                           4, pstore._deferred_names, overlap=True))
    for label, store in (("part", pstore), ("part-ov", ostore)):
        site = f"kv[{label}]"
        emit({"checked": f"{site}:plan"})
        emit_diags(audit_plan(plan, S, merge_fn=pcfg.merge,
                              site=f"{site}:plan"))

        specs0 = store.tick_arg_specs(B)
        emit({"checked": f"{site}:jaxpr[due=0]"})
        emit_diags(check_noncommit_region(
            store.raw_tick_fn(0), axis, S, specs0,
            f"{site}:jaxpr[due=0]"))

        variants = [(due, False) for due in store.supported_dues]
        if store._overlap:
            variants += [(0, True), (store.n_deferred, True)]
        for due, land in variants:
            tag = f"due={due}" + (",land" if land else "")
            prog_site = f"{site}:tick[{tag}]"
            emit({"checked": prog_site})
            vspecs = store.tick_arg_specs(B, land=land)
            _, w = walk(store.raw_tick_fn(due, land=land), vspecs)
            manifest = store.scheduled_manifest(due, land=land)
            emit_diags(placement.check_commit_walk(w, manifest, prog_site))

        don_site = f"{site}:donation"
        emit({"checked": don_site})
        hlo, _ = walk(store.raw_tick_fn(store.n_deferred), specs0,
                      donate=store.donate_argnums)
        expected = placement.donated_param_numbers(batched(specs0),
                                                   store.donate_argnums)
        emit_diags(placement.check_donation(hlo, expected, don_site,
                                            require=not on_cpu))

    emit({"done": True, "platform": jax.default_backend()})


# ---------------------------------------------------------------------------
# seeded-violation fixtures: each must trip its CC code
# ---------------------------------------------------------------------------


_SPURIOUS_HLO = """\
HloModule lint_fixture, num_partitions=8

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (p0: f32[64,2]) -> f32[512,2] {
  %p0 = f32[64,2] parameter(0)
  %ar = f32[64,2] all-reduce(%p0), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  ROOT %ag = f32[512,2] all-gather(%ar), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
}
"""

# donated params {0, 1}; the module only aliases param 1 — param 0's donated
# buffer was compiled to a copy.
_DONATION_HLO = """\
HloModule lint_fixture, input_output_alias={ {1}: (1, {}, may-alias) }, num_partitions=1

ENTRY %main (p0: f32[8,2], p1: f32[8,2]) -> (f32[8,2], f32[8,2]) {
  %p0 = f32[8,2] parameter(0)
  %p1 = f32[8,2] parameter(1)
  %c = f32[8,2] copy(%p0)
  %d = f32[8,2] add(%p1, %p1)
  ROOT %t = (f32[8,2], f32[8,2]) tuple(%c, %d)
}
"""


def fixture_checks() -> list[tuple[str, str, Callable[[], list[Diagnostic]]]]:
    """(name, expected CC code, thunk) per seeded violation."""
    import jax
    import jax.numpy as jnp

    from repro.analysis import placement
    from repro.analysis.jaxpr import (audit_plan, check_kv_tick_taint,
                                      check_noncommit_region)
    from repro.analysis.traits import certify_merge_fn
    from repro.core.ccache import StageManifest
    from repro.core.merge_functions import (ADD, MAX, dropping_add,
                                            saturating_add)
    from repro.core.merge_plan import MergePlan

    parse_plan = MergePlan.parse

    S = jax.ShapeDtypeStruct
    i32 = jnp.int32
    tbl = S((8, 2), i32)
    keys, vals = S((4,), i32), S((4, 2), i32)

    def relabel(fn, **traits):
        return dataclasses.replace(fn, **traits)

    def leaky_read_tick(settled, pendings, keys, vals):
        # privatization violation: the pending update path reads settled
        return settled, (pendings[0] + settled,)

    def leaky_write_tick(settled, pendings, keys, vals):
        # pending mass reaches the settled table on a non-commit tick
        return settled + pendings[0], (pendings[0],)

    def spurious_manifest():
        # the plan scheduled ONE fused all-reduce and nothing else
        return [StageManifest(index=0, name="chip", defer=False, stride=1,
                              fanout=8, kind="fused", fused_ops=1,
                              exchange_rounds=0, intra_rounds=0)]

    def records_fixture():
        from benchmarks.records import duplicate_record_keys
        rows = [{"bench": "kv_gups", "case": "bitwise_s8", "match": True},
                {"bench": "kv_gups", "case": "bitwise_s8", "match": False}]
        return [Diagnostic(code="CC030", site="records", message=p)
                for p in duplicate_record_keys(rows)]

    def walk_fixture(check):
        from repro.launch import hlo_cost
        w = hlo_cost.analyze_hlo(_SPURIOUS_HLO, level_sizes=(8,),
                                 level_names=("chip",))
        return check(w)

    return [
        ("trait:sat_add_declared_scalable", "CC002",
         lambda: certify_merge_fn(relabel(saturating_add(8.0), scalable=True),
                                  site="fixture:sat_add")),
        ("trait:sat_add_declared_deferrable", "CC004",
         lambda: certify_merge_fn(
             relabel(saturating_add(8.0), deferrable=True),
             site="fixture:sat_add")),
        ("trait:sat_add_huge_threshold_deferrable", "CC005",
         lambda: certify_merge_fn(
             relabel(saturating_add(1e9), deferrable=True),
             site="fixture:sat_add_1e9")),
        ("trait:drop_add_declared_deferrable", "CC006",
         lambda: certify_merge_fn(
             relabel(dropping_add(0.25), deferrable=True),
             site="fixture:drop_add")),
        ("trait:add_declared_idempotent", "CC001",
         lambda: certify_merge_fn(relabel(ADD, idempotent=True),
                                  site="fixture:add")),
        ("trait:max_declared_invertible", "CC003",
         lambda: certify_merge_fn(relabel(MAX, invertible=True),
                                  site="fixture:max")),
        ("jaxpr:collective_in_noncommit", "CC010",
         lambda: check_noncommit_region(
             lambda x: jax.lax.psum(x, "shards"), "shards", 8, (tbl,),
             "fixture:psum_region")),
        ("jaxpr:settled_read_escape", "CC011",
         lambda: check_kv_tick_taint(leaky_read_tick, "shards", 8, tbl,
                                     (tbl,), keys, vals,
                                     "fixture:leaky_read")),
        ("jaxpr:pending_escape", "CC012",
         lambda: check_kv_tick_taint(leaky_write_tick, "shards", 8, tbl,
                                     (tbl,), keys, vals,
                                     "fixture:leaky_write")),
        ("plan:defer_nondeferrable", "CC013",
         lambda: audit_plan(parse_plan("chip:2,host:4:defer"), 8,
                            merge_fn=saturating_add(8.0),
                            site="fixture:sat_defer_plan")),
        ("plan:geometry_mismatch", "CC014",
         lambda: audit_plan(parse_plan("chip:2,host:2"), 8,
                            site="fixture:bad_geometry")),
        ("hlo:collective_in_noncommit_tick", "CC020",
         lambda: walk_fixture(lambda w: placement.check_noncommit_walk(
             w, "fixture:noncommit_hlo"))),
        ("hlo:spurious_collective_vs_manifest", "CC021",
         lambda: walk_fixture(lambda w: placement.check_commit_walk(
             w, spurious_manifest(), "fixture:spurious_hlo"))),
        ("hlo:donation_fallback", "CC022",
         lambda: placement.check_donation(_DONATION_HLO, {0, 1},
                                          "fixture:donation")),
        ("records:duplicate_key", "CC030", records_fixture),
        ("durability:defer_not_checkpointed", "CC040", durability_fixture),
    ]


def durability_fixture() -> list[Diagnostic]:
    """A driver that checkpoints params/opt + only the INNERMOST pending
    level of a 2-level overlapped cascade: the outer pending and the
    in-flight launch are volatile-only — restore would drop their mass."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.durability import check_checkpoint_coverage
    from repro.checkpoint import defer_state_spec

    S = jax.ShapeDtypeStruct
    params = {"w": S((4,), jnp.int32)}
    spec = defer_state_spec(params, n_levels=2, dp=8, overlap=True)
    saved = {"params": params, "opt": {},
             "defer": {"t": spec["t"], "pending": (spec["pending"][0],)}}
    return check_checkpoint_coverage("fixture:defer_ckpt", spec, saved)


def run_fixtures() -> list[dict]:
    results = []
    for name, code, thunk in fixture_checks():
        diags = thunk()
        results.append({
            "name": name, "code": code,
            "tripped": any(d.code == code for d in diags),
            "diags": [d.as_dict() for d in diags],
        })
    return results


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def sweep_durability(report: Report) -> None:
    """CC040 over representative deferred train steps: the declared
    volatile spec (``volatile_spec``) must cover the REAL defer state the
    step initializes — drift between the two would let a checkpoint pass
    the lint while dropping mass at restore."""
    from repro.analysis.durability import check_step_durability
    from repro.core import ccache
    from repro.core.defer_schedule import DeferSchedule
    from repro.core.merge_plan import MergePlan
    from repro.runtime.chaos import ToyDeferredStep

    cases = [("chip:2,host:2:defer,pod:2:defer", (1, 2), 8, False),
             ("chip:2,host:2:defer,pod:2:defer", (2, 4), 8, True),
             ("chip:4,pod:2:defer", (4,), 8, False)]
    for spec, intervals, dp, overlap in cases:
        plan = MergePlan.parse(spec)
        names = tuple(s.name for s in ccache.deferred_stages_of(plan, dp))
        sched = DeferSchedule(names, intervals, overlap=overlap)
        step = ToyDeferredStep(plan, sched, dp, width=4)
        site = (f"durability:{spec}@dp={dp}"
                + (",overlap" if overlap else ""))
        report.mark_checked(site)
        report.extend(check_step_durability(site, step, step.init_params()))


def build_report(suppressions=(), serve: bool = True) -> Report:
    report = Report(suppressions)
    _log("trait certification sweep (standard merges)")
    sweep_merges(report)
    _log("config plan audits (production mesh geometries)")
    sweep_configs(report)
    _log("app superstep + plan lint")
    sweep_apps(report)
    _log("defer-state checkpoint coverage (CC040)")
    sweep_durability(report)
    if serve:
        _log(f"serve sweep on the forced {_SERVE_SHARDS}-way host mesh "
             f"(subprocess)")
        sweep_serve(report)
    return report


def main(argv: Optional[list] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static commutativity & collective-placement verifier "
                    "(docs/static_analysis.md)")
    p.add_argument("--json", metavar="PATH",
                   help="also write the machine-readable report/results")
    p.add_argument("--suppress", action="append", default=[],
                   metavar="CODE[@SITE]",
                   help="keep matching findings visible but non-fatal "
                        "(repeatable); e.g. CC021 or CC021@kv[all]")
    p.add_argument("--fixtures", action="store_true",
                   help="run the seeded-violation suite: every known-bad "
                        "input must trip its CC code")
    p.add_argument("--no-serve", action="store_true",
                   help="skip the forced-host-mesh serve sweep (fast "
                        "dev loop; CI runs the full sweep)")
    p.add_argument("--sub", choices=["serve"], help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.sub == "serve":
        _sub_serve()
        return 0

    if args.fixtures:
        results = run_fixtures()
        missed = [r for r in results if not r["tripped"]]
        for r in results:
            status = "TRIPPED" if r["tripped"] else "MISSED"
            print(f"fixture {r['name']}: {r['code']} {status} "
                  f"({len(r['diags'])} finding(s))")
        if args.json:
            with open(args.json, "w") as f:
                json.dump({"ok": not missed, "fixtures": results}, f,
                          indent=1)
        print(f"fixtures: {'OK' if not missed else 'FAIL'} "
              f"({len(results) - len(missed)}/{len(results)} tripped)")
        return 1 if missed else 0

    report = build_report(args.suppress, serve=not args.no_serve)
    if args.json:
        with open(args.json, "w") as f:
            f.write(report.as_json() + "\n")
    print(report.format())
    return 0 if report.ok() else 1

import os
# A CPU rehearsal of the 512-chip mesh by design: it, and every per-cell
# child it spawns, stays off any accelerator the host has.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:
  * build the step plan (steps.py) on the production mesh
  * ``jit(...).lower(**ShapeDtypeStructs).compile()`` — no allocation
  * print ``memory_analysis()`` (proves it fits) and ``cost_analysis()``
  * run the loop-aware HLO cost walk (hlo_cost.py) for FLOPs / HBM bytes /
    collective wire bytes, and derive the three roofline terms
  * write results/dryrun/<arch>__<shape>__<mesh>.json

Run one cell:     python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
Multi-pod:        ... --multipod
Everything:       python -m repro.launch.dryrun --all --mesh both
(--all spawns one subprocess per cell: device-count isolation + caching.)
"""

import argparse
import json
import subprocess
import sys
import time
import traceback


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             extra_rules: dict | None = None, tag: str = "",
             microbatches: int | None = None,
             dump_hlo: str | None = None, smoke: bool = False,
             overrides: dict | None = None) -> dict:
    import jax

    from repro.configs.base import SHAPES, ShapeConfig, get_config, \
        get_smoke_config
    from repro.launch import hlo_cost
    from repro.launch.hlo_analysis import (DCI_BW, HBM_BW, ICI_BW,
                                           PEAK_FLOPS, dci_bytes,
                                           roofline_terms)
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import plan_for

    if smoke:
        cfg = get_smoke_config(arch)
        base = SHAPES[shape]
        shape_cfg = ShapeConfig(base.name, min(base.seq_len, 512),
                                min(base.global_batch, 32), base.kind)
    else:
        cfg = get_config(arch)
        shape_cfg = SHAPES[shape]
    if overrides:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, **overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"

    rec: dict = {"arch": arch, "shape": shape, "mesh": mesh_name,
                 "chips": chips, "status": "running",
                 "kind": shape_cfg.kind}
    t0 = time.time()
    try:
        plan = plan_for(cfg, shape_cfg, mesh, extra_rules=extra_rules,
                        **({"num_microbatches": microbatches}
                           if microbatches is not None
                           and shape_cfg.kind == "train" else {}))
        lowered = plan.lower(mesh)
        rec["lower_s"] = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = time.time() - t1

        ma = compiled.memory_analysis()
        print("memory_analysis:", ma)
        mem = {k: int(getattr(ma, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes") if hasattr(ma, k)}
        live = (mem.get("argument_size_in_bytes", 0)
                + mem.get("output_size_in_bytes", 0)
                + mem.get("temp_size_in_bytes", 0)
                - mem.get("alias_size_in_bytes", 0))
        mem["live_bytes_per_device"] = live
        mem["fits_16gb_hbm"] = bool(live < 16 * 1024**3)
        rec["memory"] = mem

        ca = compiled.cost_analysis() or {}
        print("cost_analysis flops:", ca.get("flops"),
              "bytes:", ca.get("bytes accessed"))
        rec["cost_analysis_raw"] = {
            k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and k in (
                "flops", "bytes accessed", "transcendentals",
                "utilization operand 0 {}", "optimal_seconds")}

        hlo = compiled.as_text()
        if dump_hlo:
            with open(dump_hlo, "w") as f:
                f.write(hlo)
        # Per-level wire accounting: the mesh's axis nest (model-innermost
        # device order) is the physical hierarchy — chip-scope links inside
        # a model block, host-scope across the data axis, and on multipod
        # meshes the scarce inter-pod DCI on top. Collective bytes classify
        # into one vector charged at per-level rates.
        level_sizes = (mesh.shape["model"], mesh.shape["data"])
        level_names = ("chip", "host")
        if multi_pod:
            level_sizes += (mesh.shape["pod"],)
            level_names += ("pod",)
        walk = hlo_cost.analyze_hlo(
            hlo, intra_group_size=(chips // mesh.shape["pod"]
                                   if multi_pod else None),
            level_sizes=level_sizes, level_names=level_names)
        rec["hlo_walk"] = {k: walk[k] for k in
                           ("flops", "hbm_bytes", "wire_bytes", "trip_counts")}
        rec["hlo_walk"]["level_names"] = walk["level_names"]
        rec["hlo_walk"]["level_sizes"] = walk["level_sizes"]
        rec["hlo_walk"]["wire_bytes_by_level"] = walk["wire_bytes_by_level"]
        if multi_pod:
            rec["hlo_walk"]["wire_bytes_intra"] = walk["wire_bytes_intra"]
            rec["hlo_walk"]["wire_bytes_inter"] = walk["wire_bytes_inter"]
        rec["per_collective"] = walk["per_collective"]

        # The legacy 2-level intra/inter keys only exist on multipod walks;
        # record the DCI share derived from the per-level vector instead of
        # a key that defaults to zero. The roofline itself charges the
        # per-level vector directly, so no legacy split is passed to it.
        rec["hlo_walk"]["wire_bytes_inter_derived"] = dci_bytes(
            walk["wire_bytes_by_level"], walk["level_names"])
        terms = roofline_terms(walk["flops"], walk["hbm_bytes"],
                               walk["wire_bytes"],
                               wire_bytes_by_level=walk["wire_bytes_by_level"],
                               level_names=walk["level_names"])
        rec["roofline"] = terms

        # Schedule-aware defer what-if: were the scarce top level deferred
        # (merge-on-evict at pod scope), the per-level roofline picks its
        # commit interval K — report the schedule and predicted savings.
        if multi_pod and walk["wire_bytes_by_level"][-1] > 0:
            from repro.core.defer_schedule import solve_defer_schedule
            from repro.core.merge_plan import MergeLevel, MergePlan
            what_if = MergePlan(levels=tuple(
                MergeLevel(nm, sz, defer=(i == len(level_sizes) - 1))
                for i, (nm, sz) in enumerate(zip(level_names, level_sizes))))
            sched = solve_defer_schedule(
                what_if, walk["wire_bytes_by_level"], level_names,
                compute_s=terms["compute_s"], memory_s=terms["memory_s"])
            rec["defer_schedule"] = sched.as_dict()
            print("defer schedule (top level deferred):", sched.describe())
            # ... and with the launch/land overlap: the commit exchange
            # hides behind the next step's compute bound, so only its
            # exposed remainder needs amortizing — usually a smaller K.
            sched_ovl = solve_defer_schedule(
                what_if, walk["wire_bytes_by_level"], level_names,
                compute_s=terms["compute_s"], memory_s=terms["memory_s"],
                overlap=True)
            rec["defer_schedule_overlap"] = sched_ovl.as_dict()
            print("defer schedule (overlapped commit):",
                  sched_ovl.describe())

        # MODEL_FLOPS: useful-work basis. 6ND train, 2ND forward-only
        # (N_active for MoE), D = tokens processed by the step.
        n_active = cfg.n_active_params()
        if shape_cfg.kind == "train":
            tokens = shape_cfg.global_batch * shape_cfg.seq_len
            model_flops = 6.0 * n_active * tokens
        elif shape_cfg.kind == "prefill":
            tokens = shape_cfg.global_batch * shape_cfg.seq_len
            model_flops = 2.0 * n_active * tokens
        else:  # decode: one token per sequence
            tokens = shape_cfg.global_batch
            model_flops = 2.0 * n_active * tokens
        hlo_total_flops = walk["flops"] * chips
        rec["model_flops"] = model_flops
        rec["useful_flops_ratio"] = (model_flops / hlo_total_flops
                                     if hlo_total_flops else None)
        rec["hw"] = {"peak_flops": PEAK_FLOPS, "hbm_bw": HBM_BW,
                     "ici_bw": ICI_BW, "dci_bw": DCI_BW}
        rec["status"] = "ok"
        print(f"[{arch} x {shape} x {mesh_name}] "
              f"compute={terms['compute_s']:.4f}s "
              f"memory={terms['memory_s']:.4f}s "
              f"collective={terms['collective_s']:.4f}s "
              f"dominant={terms['dominant']} "
              f"useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'], 3)}")
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = repr(e)
        rec["traceback"] = traceback.format_exc()
        print(f"[{arch} x {shape} x {mesh_name}] FAILED: {e!r}",
              file=sys.stderr)
    rec["total_s"] = time.time() - t0

    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=float)
    print("wrote", path)
    return rec


def orchestrate(meshes: list[bool], out_dir: str, force: bool,
                timeout: int, only_arch: str | None = None) -> int:
    # No jax import here: each cell runs in its own subprocess.
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
    from repro.configs.base import ARCH_IDS, applicable_shapes, get_config

    failures = 0
    for arch in ARCH_IDS:
        if only_arch and arch != only_arch:
            continue
        cfg = get_config(arch)
        for shape in applicable_shapes(cfg):
            for multi_pod in meshes:
                mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
                path = os.path.join(out_dir,
                                    f"{arch}__{shape}__{mesh_name}.json")
                if os.path.exists(path) and not force:
                    with open(path) as f:
                        if json.load(f).get("status") == "ok":
                            print("cached:", path)
                            continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", out_dir]
                if multi_pod:
                    cmd.append("--multipod")
                print(">>>", " ".join(cmd), flush=True)
                try:
                    r = subprocess.run(cmd, timeout=timeout)
                    if r.returncode != 0:
                        failures += 1
                except subprocess.TimeoutExpired:
                    failures += 1
                    with open(path, "w") as f:
                        json.dump({"arch": arch, "shape": shape,
                                   "mesh": mesh_name, "status": "timeout",
                                   "timeout_s": timeout}, f)
                    print(f"TIMEOUT: {arch} x {shape} x {mesh_name}",
                          file=sys.stderr)
    return failures


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch")
    p.add_argument("--shape")
    p.add_argument("--multipod", action="store_true")
    p.add_argument("--mesh", choices=["single", "multi", "both"],
                   default="single")
    p.add_argument("--all", action="store_true")
    p.add_argument("--force", action="store_true")
    p.add_argument("--out", default="results/dryrun")
    p.add_argument("--timeout", type=int, default=3600)
    p.add_argument("--tag", default="",
                   help="suffix for experiment variants (perf iterations)")
    p.add_argument("--rules", default="",
                   help='JSON dict of extra logical->mesh rules')
    p.add_argument("--microbatches", type=int, default=None)
    p.add_argument("--dump-hlo", default=None)
    p.add_argument("--smoke", action="store_true",
                   help="reduced config on the production mesh (tests)")
    p.add_argument("--set", action="append", default=[],
                   help="config overrides, e.g. --set moe_impl=ep")
    args = p.parse_args()

    if args.all:
        meshes = {"single": [False], "multi": [True],
                  "both": [False, True]}[args.mesh]
        failures = orchestrate(meshes, args.out, args.force, args.timeout,
                               only_arch=args.arch)
        sys.exit(1 if failures else 0)

    assert args.arch and args.shape, "--arch and --shape required"
    extra_rules = json.loads(args.rules) if args.rules else None
    if extra_rules:
        extra_rules = {k: (tuple(v) if isinstance(v, list) else v)
                       for k, v in extra_rules.items()}
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        overrides[k] = v
    rec = run_cell(args.arch.replace("-", "_"), args.shape, args.multipod,
                   args.out, extra_rules=extra_rules, tag=args.tag,
                   microbatches=args.microbatches, dump_hlo=args.dump_hlo,
                   smoke=args.smoke, overrides=overrides or None)
    sys.exit(0 if rec["status"] == "ok" else 1)


if __name__ == "__main__":
    main()

"""A traced run of a cell as the program's instrumentation shows it.

    python3 bench/chip/scopes.py --workload <cell> --seed <n> --seconds <s>

One traced run as ``bench/run.py --trace 1`` makes it; prints its result
line and the window's end-to-end metrics (which a traced run's result
line leaves out), then, from the same trace: the stats the profiler gives a
module event and an op event of the first chip; the runs and device time
per chip of each program in the window; and the op time per chip in the
window summed by program and scope, and by program, scope and
instruction; and, from the host spans of both the benchmark (``bench.*``)
and the program (``repro.*``), each chip's idle gaps charged to the
innermost span at their middle, the store's own host milliseconds per
tick (``repro.kv.tick`` less its ``repro.kv.dispatch``) and the front
end's per step (``repro.frontend.step`` less the ``repro.kv.tick`` in it).
The benchmark's reduced trace keeps only its own spans, so these are read
here from the trace file.

An op event names its HLO instruction and carries no scope path, so each
op is put in the program whose run holds its start, and its scope is read
from that program's compiled HLO: the first part of the instruction's
``op_name`` after ``jit(<program>)/``, as ``jax.named_scope`` in
``serve/kv.py`` sets it (``scatter``, ``identity``, ...), or ``-`` where
the path has none (the shard axis the mesh executor strips and adds). A
fusion carries its root's ``op_name``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import pathlib
import re
import shutil
import sys
import tempfile
import time

T_PROCESS = time.perf_counter()
_OP_NAME = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = [^\n]*?"
                      r"op_name=\"([^\"]*)\"", re.M)


def scope_of(program: str, op_name: str) -> str:
    """The named scope of an instruction's ``op_name`` in ``program``."""
    parts = op_name.split(";")[0].split("/")
    if parts[0] != f"jit({program})":
        return "-"
    parts = parts[1:]
    if parts and parts[0] == "shard_map":
        parts = parts[1:]
    return parts[0] if len(parts) > 1 else "-"


def op_names(store, batch: int, devices: list) -> dict:
    """``{program: {instruction: op_name}}`` of the store's programs,
    compiled for ``devices``."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from bench.selftest.compile_rehearsal import programs
    S = store.n_shards
    shard = NamedSharding(Mesh(np.asarray(devices[:S]), ("shards",)),
                          P("shards"))
    out = {}
    for _, fn, specs, donate in programs(store, batch):
        args = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            (S,) + s.shape, s.dtype, sharding=shard), specs)
        text = store.spmd.lower(fn, *args, donate=donate).compile().as_text()
        out[fn.__name__] = dict(_OP_NAME.findall(text[text.index("\nENTRY"):]))
    return out


def _program(module_event) -> str:
    return module_event.name.partition("(")[0].removeprefix("jit_")


def host_view(path: str, n_chips: int) -> dict:
    """Idle gaps and host milliseconds from all the host spans."""
    from jax.profiler import ProfileData
    from bench.programs import host_ms_less_children
    from bench.trace import Event, idle_by_host, load
    spans = [Event(e.name, float(e.start_ns), float(e.end_ns))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for ln in plane.lines for e in ln.events
             if e.name.startswith(("bench.", "repro."))]
    t = dataclasses.replace(load(path, n_chips), spans=sorted(
        spans, key=lambda s: s.start_ns))
    return {"idle_gaps": idle_by_host(t.ops, t.spans, t.window),
            "store_host_ms_per_tick": host_ms_less_children(
                t, "repro.kv.tick", "repro.kv.dispatch"),
            "frontend_step_host_ms_per_tick": host_ms_less_children(
                t, "repro.frontend.step", "repro.kv.tick")}


def breakdown(path: str, n_chips: int, window, names: dict) -> dict:
    """What the trace at ``path`` gives, per chip in ``window``
    (``(start_ns, end_ns)``), with scopes from ``names``."""
    from jax.profiler import ProfileData
    from bench.trace import parse_hlo
    planes = {}
    for plane in ProfileData.from_file(path).planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            planes[int(m.group(1))] = {ln.name: ln for ln in plane.lines}
    chips = [planes[c] for c in sorted(planes)[:n_chips]]
    out = {}
    if not chips:
        return out

    def clipped(e):
        return (min(float(e.end_ns), window[1])
                - max(float(e.start_ns), window[0]))

    for key, line in (("module_event", "XLA Modules"), ("op_event", "XLA Ops")):
        e = next(iter(chips[0][line].events))
        out[key] = {"name": e.name[:200], **dict(e.stats)}
    runs, run_ns = collections.Counter(), collections.Counter()
    by_scope, by_instr = collections.Counter(), collections.Counter()
    for lines in chips:
        mods = sorted(lines["XLA Modules"].events,
                      key=lambda e: float(e.start_ns))
        starts = [float(e.start_ns) for e in mods]
        for e in mods:
            if clipped(e) > 0:
                runs[_program(e)] += 1
                run_ns[_program(e)] += clipped(e)
        for e in lines["XLA Ops"].events:
            dur = clipped(e)
            if dur <= 0:
                continue
            i = bisect.bisect_right(starts, float(e.start_ns)) - 1
            program = _program(mods[i]) if i >= 0 else "?"
            instr = parse_hlo(e.name)[0]
            scope = scope_of(program, names.get(program, {}).get(instr, ""))
            by_scope[f"{program}/{scope}"] += dur
            by_instr[f"{program}/{scope}/{instr}"] += dur
    n = len(chips)
    out["programs"] = {k: [runs[k] / n, run_ns[k] / n / 1e9] for k in runs}
    out["seconds"] = {k: v / n / 1e9 for k, v in by_scope.most_common()}
    out["instructions"] = {k: v / n / 1e9
                           for k, v in by_instr.most_common(40)}
    return out


def main(argv) -> int:
    import argparse
    root = pathlib.Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import harness, kvstore
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    devices = harness.find_chips(cell.workload["chips"])
    peaks = harness.load_peaks(devices[0].device_kind)

    with tempfile.TemporaryDirectory() as keep:
        kept = pathlib.Path(keep) / "run.xplane.pb"
        real_load, window = harness._load_trace, []

        def load_trace(tdir, n_chips):
            reduced = real_load(tdir, n_chips)
            shutil.copy(next(pathlib.Path(tdir).rglob("*.xplane.pb")), kept)
            window.append(reduced.window)
            return reduced
        harness._load_trace = load_trace
        driver = harness.load_module("drivers", cell.config["driver"])
        real_window, e2e = driver.System.run_window, {}

        def run_window(self, *a, **k):
            metrics, counters = real_window(self, *a, **k)
            e2e.update(metrics)
            return metrics, counters
        driver.System.run_window = run_window

        out = harness.run_cell(cell, args.seed, args.seconds, True,
                               devices, peaks, T_PROCESS)
        print(json.dumps(out), flush=True)
        print(json.dumps({"window": e2e}), flush=True)
        store = kvstore.make_store(cell.config, devices)
        names = op_names(store, cell.config["slots_per_shard"], devices)
        del store
        found = breakdown(str(kept), cell.workload["chips"], window[0],
                          names)
        found["host"] = host_view(str(kept), cell.workload["chips"])
    print(json.dumps(found, indent=1, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

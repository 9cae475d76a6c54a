"""One run of one benchmark cell: set-up, a timed window, the check.

Everything a cell needs is found by name from ``BENCHMARK.json``:
  - the cell (``workloads``) names a configuration and a traffic mix;
  - the configuration's file names its driver, ``bench/drivers/<driver>.py``;
  - the traffic mix is the data file ``bench/traffic/<name>.json``;
  - each per-layer metric is ``bench/metrics/<name>.py``, whose
    ``read(run)`` returns a number, or ``None`` when it finds nothing.
A driver module has ``traffic(config, mix, seed)``, which generates the
inputs, ``build(config, devices)``, which returns the system with every
program its traffic uses warmed up, and ``check(config, inputs, outputs)``,
which compares the outputs with the plain reference. The system has
``run_window(inputs, seconds, span)``, which returns the window's
end-to-end metrics and its counters, and ``outputs(inputs, seed)``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import pathlib
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class SetupError(RuntimeError):
    """The run cannot start here: no chip, too few chips, an unknown
    device, or a name that nothing in ``bench/`` defines."""


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def _json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SetupError(f"{path.relative_to(ROOT)} does not exist")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module, loaded once."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise SetupError(f"{path.relative_to(ROOT)} does not exist")
    key = f"bench.{kind}.{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or load_benchmark()
    wl = {w["name"]: w for w in bench["workloads"]}.get(name)
    if wl is None:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = _json(ROOT / entry["file"])
    traffic = _json(BENCH / "traffic" / f"{wl['traffic']}.json")

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(wl, config, traffic,
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)])


def load_peaks(device_kind: str) -> dict:
    peaks = _json(BENCH / "peaks.json")["devices"]
    if device_kind not in peaks:
        raise SetupError(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json ({sorted(peaks)})")
    return peaks[device_kind]


def find_chips(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(f"no TPU: JAX's default backend is "
                         f"{devs[0].platform!r}; this benchmark measures a "
                         f"TPU only")
    if len(devs) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs


@dataclasses.dataclass
class Run:
    """What a per-layer metric reads: the reduced trace, the run's
    counters and the chip's peaks."""

    trace: object
    counters: dict
    peaks: dict


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed path in
    the checkout (or ``$JAX_COMPILATION_CACHE_DIR``), for every program,
    so that only a cell's first run in a checkout compiles."""
    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class _CompileCount:
    """Counts XLA backend compiles while ``on``."""

    def __init__(self):
        import jax
        self.n, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@functools.cache
def _compiles() -> _CompileCount:
    return _CompileCount()


def judge(checks: dict) -> bool:
    """``correct``: every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices: list, peaks: dict, t_process: float) -> dict:
    """Set up, measure, check; returns the result line's object."""
    import jax

    cfg = cell.config
    devices = devices[:cell.workload["chips"]]
    driver = load_module("drivers", cfg["driver"])
    inputs = driver.traffic(cfg, cell.traffic, seed)
    system = driver.build(cfg, devices)
    setup_s = time.perf_counter() - t_process

    compiles = _compiles()
    compiles.n = 0
    span = jax.profiler.TraceAnnotation if trace else None
    with contextlib.ExitStack() as stack:
        if trace:
            tdir = stack.enter_context(tempfile.TemporaryDirectory())
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        compiles.on = True
        e2e, counters = system.run_window(inputs, seconds, span)
        compiles.on = False
        if trace:
            jax.profiler.stop_trace()
            reduced = _load_trace(tdir, len(devices))
            if devices[0].platform == "tpu" and not any(reduced.ops):
                raise RuntimeError("the trace holds no op of the cell's "
                                   "chips")
    counters["compiles_in_window"] = compiles.n
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)

    outputs = system.outputs(inputs, seed)
    del system
    gc.collect()
    checks, attempted, failed = driver.check(cfg, inputs, outputs)
    correct = judge(checks)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    if trace:
        from bench.trace import busy, idle_by_host, top_ops, total
        run = Run(reduced, counters, peaks)
        metrics = {}
        for m in cell.per_layer:
            value = load_module("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = sum(total(busy(ops, reduced.window))
                               for ops in reduced.ops) / len(devices) / 1e9
        device["window_s"] = reduced.window_ns / 1e9
        breakdown = {"device_ops": top_ops(reduced.ops, reduced.window),
                     "idle_gaps": idle_by_host(reduced.ops, reduced.spans,
                                               reduced.window)}
    else:
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        breakdown = None
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compiles_in_window"] = counters["compiles_in_window"]
    out["checks"] = checks
    return out


def _load_trace(tdir: str, n_chips: int):
    from bench.trace import load
    found = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under the trace "
                           f"directory, found {len(found)}")
    return load(str(found[0]), n_chips)


def main(argv, t_process: float) -> int:
    import argparse
    p = argparse.ArgumentParser(description="Run one benchmark cell.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise SetupError(f"no program under {ROOT / 'src'}: run from "
                             f"a checkout of the repository")
        sys.path.insert(0, str(ROOT / "src"))
        cell = load_cell(args.workload)
        enable_compile_cache()
        devices = find_chips(cell.workload["chips"])
        peaks = load_peaks(devices[0].device_kind)
    except SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices, peaks, t_process)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0

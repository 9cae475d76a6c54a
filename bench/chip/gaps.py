"""Host stalls in a run of a cell: the longest waits between store ticks.

    python3 bench/chip/gaps.py --workload <cell> --seed <n> --seconds <s>

One untraced run as ``bench/run.py`` makes it, with every ``ShardedKV.tick``
call and every garbage collection timed on the host. Prints the run's
result line, then the three longest waits between the window's tick calls
(their start in the window, wall seconds, and the process's CPU seconds in
them) and every collection over 20 ms in the window. A wait that holds
little CPU time is a stall of the whole process; one that holds a
collection is the collector.
"""

from __future__ import annotations

import gc
import json
import pathlib
import sys
import time

T_PROCESS = time.perf_counter()


def main(argv) -> int:
    import argparse
    root = pathlib.Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import harness
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    devices = harness.find_chips(cell.workload["chips"])
    peaks = harness.load_peaks(devices[0].device_kind)

    from repro.serve.kv import ShardedKV
    ticks, pauses, window = [], [], []
    real_tick = ShardedKV.tick

    def tick(self, keys, vals):
        ticks.append((time.perf_counter(), time.process_time()))
        return real_tick(self, keys, vals)
    ShardedKV.tick = tick

    def collected(phase, info):
        if phase == "start":
            collected.t = time.perf_counter()
        else:
            pauses.append((collected.t, time.perf_counter() - collected.t,
                           info["generation"]))
    gc.callbacks.append(collected)

    driver = harness.load_module("drivers", cell.config["driver"])
    real_window = driver.System.run_window

    def run_window(self, *a, **k):
        window.append(time.perf_counter())
        out = real_window(self, *a, **k)
        window.append(time.perf_counter())
        return out
    driver.System.run_window = run_window

    out = harness.run_cell(cell, args.seed, args.seconds, False, devices,
                           peaks, T_PROCESS)
    print(json.dumps(out), flush=True)
    t0, t1 = window
    inside = [t for t in ticks if t0 <= t[0] <= t1]
    waits = sorted(((b[0] - a[0], b[1] - a[1], a[0] - t0)
                    for a, b in zip(inside, inside[1:])), reverse=True)
    for wall, cpu, at in waits[:3]:
        print(f"wait at {at:.3f} s: wall {wall:.4f} s, cpu {cpu:.4f} s")
    for at, dur, gen in pauses:
        if t0 <= at <= t1 and dur > 0.02:
            print(f"collection at {at - t0:.3f} s: gen {gen}, {dur:.4f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

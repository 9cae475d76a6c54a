"""The readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --program-seeds 1,2,... --control-seeds 7,8,9

In one process that holds the cell's chips: the program's readings, one
short run of the cell per program seed, and the control's. The control is
the plain reference put in the program's place and computed in the nearest
lower precision a later change might be tempted by: float32 sums (24 bits
of mantissa) where the cell states int32. It takes a whole generated block
of the cell's traffic per shard and the run's sample of gets, and the
cell's driver judges its outputs with the same ``check`` as the program's.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np


def control_table(n_keys: int, cols: int, parts) -> np.ndarray:
    """The sums of ``(keys, vals, times)`` parts accumulated in float32 by
    JAX's scatter-add on the default device, rounded to int32 with
    wrap-around."""
    import jax.numpy as jnp
    from bench.reference import wrap_int32
    acc = jnp.zeros((n_keys, cols), jnp.float32)
    for keys, vals, times in parts:
        ok = keys >= 0
        k = jnp.asarray(keys[ok])
        v = jnp.asarray(vals[ok].astype(np.float32))
        for _ in range(times):
            acc = acc.at[k].add(v)
    wide = np.rint(np.asarray(acc, np.float64)).astype(np.int64)
    return wrap_int32(wide)


def control_readings(cell, seed: int) -> dict:
    """The control's outputs, judged by the cell's driver as the program's
    are: the whole generated block of every shard's stream sent, and the
    run's sample of gets answered from the control's table."""
    from bench import harness, kvstore
    from bench.generate import consumed
    cfg = cell.config
    driver = harness.load_module("drivers", cfg["driver"])
    inputs = driver.traffic(cfg, cell.traffic, seed)
    stream = inputs.stream
    sent = [stream.length] * cfg["shards"]
    parts = [p for s, n in enumerate(sent) for p in consumed(stream, s, n)]
    table = control_table(cfg["n_keys"], cfg["cols"], parts)
    gk = kvstore.get_keys(cfg, stream, seed)
    out = {"sent": sent, "table": table, "get_keys": gk,
           "answers": [table[k] for k in gk]}
    checks, attempted, failed = driver.check(cfg, inputs, out)
    return {"correct": harness.judge(checks), "attempted": attempted,
            "failed": failed,
            "checks": {k: v["value"] for k, v in checks.items()}}


def main(argv) -> int:
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import harness
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--program-seeds", default="")
    p.add_argument("--control-seeds", default="")
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    devices = harness.find_chips(cell.workload["chips"])
    peaks = harness.load_peaks(devices[0].device_kind)
    for seed in [int(s) for s in args.program_seeds.split(",") if s]:
        out = harness.run_cell(cell, seed, args.seconds, False, devices,
                               peaks, time.perf_counter())
        print(json.dumps({"who": "program", "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "updates_per_s":
                              out["metrics"]["updates_per_s"]["value"],
                          "checks": {k: v["value"] for k, v in
                                     out["checks"].items()}}), flush=True)
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        print(json.dumps({"who": "control", "seed": seed,
                          **control_readings(cell, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

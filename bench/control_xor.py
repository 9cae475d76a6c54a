"""The control reading of the XOR cell's limits: the plain reference in the
nearest lower precision.

    python3 bench/control_xor.py --workload hpcc4.xor-uniform --seeds 7,8,9

The configuration's word is 64 bits, two int32 lanes; the control keeps
32-bit words: each update's low lane XORed in, its high lane dropped. It
takes one whole pass through the generated block of every chip's stream
and the run's sample of gets, and the cell's driver judges its outputs
with the same ``check`` as the program's. Numpy on the host: no chip is
needed, and the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np


def control_rows(cols: int, parts):
    """The reference over 32-bit words, as the rows that are not zero: the
    low lane of every ``(keys, vals)`` part XORed in, the other lanes left
    zero."""
    from bench.reference_xor import expected_rows
    keys, low = expected_rows(1, ((k, np.asarray(v)[..., :1])
                                  for k, v in parts))
    return keys, np.concatenate(
        [low, np.zeros((len(keys), cols - 1), np.int32)], axis=1)


def control_readings(cell, seed: int) -> dict:
    """The control's outputs for one pass, judged by the cell's driver as
    the program's are."""
    from bench import harness, kvstore
    from bench.reference_xor import lookup
    cfg = cell.config
    driver = harness.load_module("drivers", cfg["driver"])
    inputs = driver.traffic(cfg, cell.traffic, seed)
    T = len(inputs.keys)
    rows = control_rows(cfg["cols"], driver.sent(inputs, T))
    gk = kvstore.get_keys(cfg, inputs.stream, seed)
    out = {"sent": [T * cfg["slots_per_shard"]] * cfg["shards"],
           "rows": rows, "get_keys": gk,
           "answers": list(lookup(*rows, gk))}
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
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        print(json.dumps({"who": "control", "seed": seed,
                          **control_readings(cell, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

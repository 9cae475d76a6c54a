"""Medians and spreads of result lines, in sets of ``n`` runs.

    python3 bench/chip/spread.py <file of result lines> [n]

The spread is the distance between the first and third quartiles, by
``statistics.quantiles(values, n=4)``, over the median.
"""

import json
import statistics
import sys


def main(path: str, n: int) -> None:
    values = {}
    with open(path) as f:
        for line in f:
            if not line.startswith('{"correct"'):
                continue
            d = json.loads(line)
            print(d["correct"], d["attempted"], d["failed"],
                  {k: v["value"] for k, v in d["metrics"].items()},
                  d["device"].get("memory_peak_bytes"),
                  d.get("compiles_in_window"))
            for k, v in d["metrics"].items():
                values.setdefault(k, []).append(v["value"])
    for k, vals in values.items():
        for i in range(0, len(vals), n):
            g = vals[i:i + n]
            if len(g) < 2:
                continue
            q = statistics.quantiles(g, n=4)
            med = statistics.median(g)
            print(f"{k}: set {i // n + 1} n={len(g)} median={med} "
                  f"spread={(q[2] - q[0]) / med} min={min(g)} max={max(g)}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 6)

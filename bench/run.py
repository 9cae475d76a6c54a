"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with the reference, beside its limit. The
same checks are the last lines of standard error. Without a TPU, with fewer
chips than the cell asks for, or on a chip that ``bench/peaks.json`` does
not list, it prints no result and exits with 2.
"""

import time

T_PROCESS = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# import the benchmark as the package ``bench``: its modules then never
# shadow the standard library's (``trace``)
sys.path[0] = str(ROOT)

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_PROCESS))

"""``bench/chip/scopes.py`` for the HPCC cell, whose store only its own
driver builds.

    python3 bench/chip/scopes_xor.py --workload hpcc4.xor-uniform --seed <n> --seconds <s>
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, kvstore  # noqa: E402
from bench.chip import scopes  # noqa: E402

if __name__ == "__main__":
    kvstore.make_store = harness.load_module("drivers",
                                             "hpcc_direct").make_store
    sys.exit(scopes.main(sys.argv[1:]))

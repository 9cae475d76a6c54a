"""The benchmark's own checks, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/selftest

Four host devices stand in for the four-chip host; nothing here measures a
time.
"""

import os
import pathlib
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

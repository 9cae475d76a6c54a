"""``bench/run.py`` prints no result and exits non-zero where it cannot
measure: no TPU, a chip that ``peaks.json`` does not list, a directory
without the program, a cell nobody defined."""

import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

RUN = ["--workload", "kv1.ingest-uniform", "--seed", "1", "--seconds", "1",
       "--trace", "0"]


def run(root, args=RUN):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = run(harness.ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_unknown_cell_no_result():
    args = list(RUN)
    args[1] = "no-such.cell"
    p = run(harness.ROOT, args)
    assert p.returncode != 0 and p.stdout == ""


def test_a_directory_of_only_the_benchmark_no_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_a_device_kind_missing_from_the_peaks_is_refused():
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.SetupError):
        harness.load_peaks("TPU v4")

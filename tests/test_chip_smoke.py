"""``chip_smoke.py`` refuses to report without a TPU or without the repo,
and its four-chip train check catches a wrong gradient merge."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_chip_smoke_fails_without_tpu():
    r = _run(SCRIPT, ROOT)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert _no_result(r.stdout)


def test_chip_smoke_fails_alone(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    r = _run(str(alone), str(tmp_path))
    assert r.returncode != 0
    assert _no_result(r.stdout)


# The four-chip train check at smoke size on four host devices: the merge
# plan passes, and each planted merge fault fails it.
_FOUR_TRAIN = r"""
import sys
import jax
import jax.numpy as jnp
from jax import lax

import chip_smoke
from repro.launch import steps

chip_smoke.TRAIN_ARGV += ["--smoke", "--seq", "32"]
fault, merge = sys.argv[1], steps.merge_gradients


def faulty(grads, axis, **kw):
    n = lax.axis_size(axis)
    if fault == "half_ranks":
        keep = lax.axis_index(axis) < n // 2
        grads = jax.tree.map(lambda g: jnp.where(keep, g * 2, 0), grads)
    if fault == "one_rank":  # the mean of the other ranks
        keep = lax.axis_index(axis) > 0
        grads = jax.tree.map(lambda g: jnp.where(keep, g * n / (n - 1), 0),
                             grads)
    merged = merge(grads, axis, **kw)
    if fault == "zero":
        return jax.tree.map(jnp.zeros_like, merged)
    if fault == "sum":
        return jax.tree.map(lambda g: g * n, merged)
    return merged


steps.merge_gradients = faulty
try:
    print(chip_smoke.four_train_phase(0))
except chip_smoke.PhaseError as e:
    print(f"REFUSED: {e}")
"""


@pytest.mark.parametrize("fault",
                         ["none", "zero", "half_ranks", "one_rank", "sum"])
def test_four_chip_train_check_catches_merge_faults(fault, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    r = subprocess.run([sys.executable, "-c", _FOUR_TRAIN, fault], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    last = r.stdout.strip().splitlines()[-1]
    if fault == "none":
        assert last.startswith("dp=4 plan=chip:2,host:2"), last
    else:
        assert last.startswith("REFUSED: merge plan"), last

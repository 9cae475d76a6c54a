"""End-to-end integration: training reduces loss, checkpoint-resume is
deterministic, the plan machinery lowers+compiles, CLIs run."""

import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig, get_smoke_config
from repro.data.pipeline import batch_at, data_config_for
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (lowering_rules, make_train_step, plan_for)
from repro.models.module import split_params
from repro.models.registry import build_model
from repro.optim import adamw, constant, make_optimizer
from repro.sharding.partition import sharding_rules

ENV = dict(os.environ, PYTHONPATH=os.pathsep.join(
    [os.path.abspath("src"), os.environ.get("PYTHONPATH", "")]))


def _train(arch="xlstm_125m", steps=25, seed=0):
    cfg = get_smoke_config(arch)
    shape = ShapeConfig("t", 32, 4, "train")
    model = build_model(cfg)
    opt = adamw(constant(3e-3))
    step_fn = jax.jit(make_train_step(model, cfg, opt, 1))
    params, _ = split_params(model.init(jax.random.key(seed)))
    state = {"params": params, "opt": opt.init(params)}
    dcfg = data_config_for(cfg, shape, seed=seed)
    losses = []
    for i in range(steps):
        batch = jax.tree.map(jnp.asarray, batch_at(dcfg, i))
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    return state, losses


def test_training_reduces_loss():
    _, losses = _train(steps=25)
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3


def test_checkpoint_resume_bit_identical():
    from repro import checkpoint as ckpt
    cfg = get_smoke_config("qwen1_5_0_5b")
    shape = ShapeConfig("t", 32, 4, "train")
    model = build_model(cfg)
    opt = adamw(constant(1e-3))
    step_fn = jax.jit(make_train_step(model, cfg, opt, 1))
    params, _ = split_params(model.init(jax.random.key(0)))
    state = {"params": params, "opt": opt.init(params)}
    dcfg = data_config_for(cfg, shape, seed=0)

    def run(state, lo, hi):
        for i in range(lo, hi):
            batch = jax.tree.map(jnp.asarray, batch_at(dcfg, i))
            state, _ = step_fn(state, batch)
        return state

    full = run(state, 0, 10)
    with tempfile.TemporaryDirectory() as d:
        mid = run(state, 0, 5)
        ckpt.save(d, 5, mid, extras={"next_step": 5})
        restored, extras = ckpt.restore(d, mid)
        resumed = run(restored, extras["next_step"], 10)
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(resumed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind,shape", [
    ("train", ShapeConfig("train_t", 64, 4, "train")),
    ("prefill", ShapeConfig("prefill_t", 64, 4, "prefill")),
    ("decode", ShapeConfig("decode_t", 64, 4, "decode")),
])
def test_plan_lowers_and_compiles_single_device(kind, shape):
    cfg = get_smoke_config("internlm2_1_8b")
    mesh = make_host_mesh(1, 1)
    plan = plan_for(cfg, shape, mesh)
    compiled = plan.lower(mesh).compile()
    assert compiled.cost_analysis() is not None


def test_microbatched_plan_matches_loss():
    """Grad accumulation (CCache soft-merge) == direct whole-batch grads."""
    cfg = get_smoke_config("granite_34b")
    shape = ShapeConfig("t", 32, 8, "train")
    model = build_model(cfg)
    opt = adamw(constant(1e-3))
    params, _ = split_params(model.init(jax.random.key(0)))
    state = {"params": params, "opt": opt.init(params)}
    dcfg = data_config_for(cfg, shape, seed=0)
    batch = jax.tree.map(jnp.asarray, batch_at(dcfg, 0))

    s1 = jax.jit(make_train_step(model, cfg, opt, 1))
    s4 = jax.jit(make_train_step(model, cfg, opt, 4))
    out1, m1 = s1(state, batch)
    out4, m4 = s4(state, batch)
    # losses computed over the same tokens; microbatched is the mean of
    # per-microbatch means (equal sizes -> equal), grads averaged.
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 5e-3
    for a, b in zip(jax.tree.leaves(out1["params"]),
                    jax.tree.leaves(out4["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-2)


@pytest.mark.slow
def test_train_cli_end_to_end():
    with tempfile.TemporaryDirectory() as d:
        cmd = [sys.executable, "-m", "repro.launch.train", "--arch",
               "xlstm-125m", "--smoke", "--steps", "6", "--batch", "2",
               "--seq", "32", "--ckpt-dir", d, "--ckpt-every", "3"]
        r = subprocess.run(cmd, env=ENV, capture_output=True, text=True,
                           timeout=600)
        assert r.returncode == 0, r.stderr[-2000:]
        assert "loss" in r.stdout
        # resume path
        r2 = subprocess.run(cmd + ["--steps", "8"], env=ENV,
                            capture_output=True, text=True, timeout=600)
        assert r2.returncode == 0, r2.stderr[-2000:]
        assert "resumed from checkpoint" in r2.stdout


@pytest.mark.slow
def test_serve_cli_end_to_end():
    cmd = [sys.executable, "-m", "repro.launch.serve", "--arch",
           "qwen1-5-0-5b", "--smoke", "--batch", "2", "--prompt-len", "16",
           "--gen", "4"]
    r = subprocess.run(cmd, env=ENV, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "tok/s" in r.stdout


@pytest.mark.slow
def test_dryrun_smoke_cell_on_production_mesh():
    """A reduced config lowered on the real 512-device multi-pod mesh —
    exercises the full dry-run path in CI time."""
    with tempfile.TemporaryDirectory() as d:
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch",
               "internlm2-1-8b", "--shape", "train_4k", "--smoke",
               "--multipod", "--out", d]
        r = subprocess.run(cmd, env=ENV, capture_output=True, text=True,
                           timeout=900)
        assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
        assert "dominant=" in r.stdout

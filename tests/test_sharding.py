"""Logical-axis sharding rules: divisibility fallback + conflict guard."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_host_mesh
from repro.sharding.partition import (DEFAULT_RULES, logical_constraint,
                                      sharding_rules, spec_for)


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh(1, 1)


class FakeMesh:
    """Shape-only stand-in so we can test 16x16 logic on one device."""

    def __init__(self, **shape):
        self.shape = shape


M = FakeMesh(data=16, model=16)
MP = FakeMesh(pod=2, data=16, model=16)


def test_spec_basic_rules():
    assert spec_for((151936, 1024), ("vocab", "embed"), M) == \
        P("model", "data")
    assert spec_for((1024, 2816), ("embed", "mlp"), M) == P("data", "model")
    # odd vocab falls back to replicated
    assert spec_for((151937, 1024), ("vocab", "embed"), M) == \
        P(None, "data")


def test_spec_divisibility_fallback():
    # 8 kv heads on a 16-way model axis stay replicated
    assert spec_for((2, 128, 8, 128),
                    ("batch", "cache_seq", "kv_heads", "head_dim"), M) == \
        P(None, None, None, None)
    assert spec_for((2, 128, 16, 128),
                    ("batch", "cache_seq", "kv_heads", "head_dim"), M) == \
        P(None, None, "model", None)


def test_spec_composite_batch_axis():
    # multi-pod: batch -> ("pod", "data"); divisible prefix kept
    assert spec_for((256, 4096), ("batch", "seq"), MP) == \
        P(("pod", "data"), None)
    # batch=2: only pod divides
    assert spec_for((2, 4096), ("batch", "seq"), MP) == P(("pod",), None)
    # batch=1: nothing divides
    assert spec_for((1, 4096), ("batch", "seq"), MP) == P(None, None)


def test_spec_conflict_guard():
    # both dims resolve to "model": the second one must be dropped
    rules = {"cache_seq": "model"}
    assert spec_for((2, 4096, 16, 128),
                    ("batch", "cache_seq", "kv_heads", "head_dim"), M,
                    rules) == P(None, "model", None, None)


def test_logical_constraint_noop_outside_ctx():
    x = jnp.ones((4, 4))
    y = logical_constraint(x, ("batch", "embed"))
    assert y is x


def test_logical_constraint_in_ctx(mesh):
    x = jnp.ones((4, 4))
    with sharding_rules(mesh):
        y = jax.jit(lambda a: logical_constraint(a, ("batch", "embed_act")))(x)
    assert y.shape == (4, 4)


def test_lowering_rules_decode_kv_fallback():
    from repro.configs.base import SHAPES, get_config
    from repro.launch.steps import lowering_rules
    shape = SHAPES["decode_32k"]
    # granite: MQA kv=1 -> cache on sequence
    r = lowering_rules(get_config("granite_34b"), shape, M)
    assert r.get("cache_seq") == "model" and r.get("kv_heads") is None
    # qwen1.5: kv=16 divides -> keep kv sharding
    r = lowering_rules(get_config("qwen1_5_0_5b"), shape, M)
    assert "cache_seq" not in r


def test_lowering_rules_seq_parallel_gate():
    from repro.configs.base import SHAPES, get_config
    from repro.launch.steps import lowering_rules
    shape = SHAPES["train_4k"]
    assert lowering_rules(get_config("llama3_405b"), shape, M).get(
        "seq_res") == "model"
    assert "seq_res" not in lowering_rules(get_config("qwen1_5_0_5b"),
                                           shape, M)
    # giants also get pod-level FSDP
    assert lowering_rules(get_config("kimi_k2_1t"), shape, MP).get(
        "embed") == ("pod", "data")

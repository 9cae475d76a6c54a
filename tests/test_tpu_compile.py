"""The store's scatter kernel compiles for a described TPU v5e chip.

Nothing runs here: the TPU compiler, installed with jaxlib, compiles for a
``v5e:2x2`` topology that is described, not attached. Shapes are the
store's real widths (a 2^22 x 4 table, 1024 updates per tick) and the tile
is the one ``cscatter`` chooses by default. The topology is described
inside a fixture (never at import), and the persistent compilation cache is
off around the compiles: entries written for a chip that is not attached
cannot be read back here.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.kernels.cscatter import cscatter

ROWS, COLS, BATCH = 1 << 22, 4, 1024
# the benchmark cells' table: 2^23 x 4 int32 counters a chip
STORE_ROWS = 1 << 23


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("kind,dtype", [
    ("add", jnp.int32),
    ("add", jnp.float32),
    ("max", jnp.int32),
    ("min", jnp.int32),
    ("or", jnp.int32),
])
def test_cscatter_compiles_for_v5e(one_chip, no_persistent_cache, kind,
                                   dtype):
    table = jax.ShapeDtypeStruct((ROWS, COLS), dtype, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((BATCH, COLS), dtype, sharding=one_chip)
    compiled = jax.jit(
        lambda t, i, v: cscatter(t, i, v, kind=kind, interpret=False)
    ).lower(table, ids, vals).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", [1024, 8192])
def test_cscatter_updates_table_in_place_for_v5e(one_chip, no_persistent_cache,
                                                 batch):
    """At the cells' shapes (a tick's 1,024 ids, a commit ring's 8,192) the
    kernel's custom call aliases its table operand to its output: blocks
    no id touches are neither read nor written."""
    table = jax.ShapeDtypeStruct((STORE_ROWS, COLS), jnp.int32,
                                 sharding=one_chip)
    ids = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((batch, COLS), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda t, i, v: cscatter(t, i, v, interpret=False), donate_argnums=0
    ).lower(table, ids, vals).compile()
    (call,) = [line for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line]
    # operands: item blocks, item positions, item count, ids, vals, table
    assert "output_to_operand_aliasing={{}: (5, {})}" in call


def test_store_sync_tick_copies_no_more_tables_for_v5e(
        topo, no_persistent_cache, monkeypatch):
    """The one-chip store's tick (2^23 keys, 1,024 updates) holds at most
    two table-sized copies, the relayouts of the settled table into the
    kernel's 128-lane layout and back, and no third: the kernel updates
    the identity table it is given in place."""
    from repro.apps.sharded import mesh_spmd
    from repro.core.merge_functions import ADD
    from repro.serve import KVConfig, ShardedKV, serving_plan

    mesh = Mesh(np.asarray(topo.devices[:1]), ("shards",))
    store = ShardedKV(
        KVConfig(n_keys=STORE_ROWS, cols=COLS, dtype=jnp.int32, merge=ADD,
                 consistency="eventual", engine="kernel"),
        1, mesh_spmd(mesh), plan=serving_plan(1, "all"))
    shard = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
        "shards"))
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((1,) + s.shape, s.dtype,
                                       sharding=shard),
        store.tick_arg_specs(BATCH))
    # the store picks the Pallas kernel on a TPU backend only
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = store.spmd.lower(store.raw_tick_fn(), *args,
                           donate=(0,)).compile().as_text()
    assert "tpu_custom_call" in hlo
    copies = re.findall(
        rf"= s32\[(?:1,)?{STORE_ROWS},{COLS}\]\S* copy(?:-start)?\(", hlo)
    assert len(copies) <= 2, copies


@pytest.mark.parametrize("batch", [1024, 32768])
def test_cscatter_xor_compiles_for_v5e(one_chip, no_persistent_cache, batch):
    """A chip's share of HPCC's table, 2^29 64-bit words packed 64 to a
    128-lane int32 line, takes the XOR kind in place, with a tick's and a
    routed commit's updates."""
    table = jax.ShapeDtypeStruct((STORE_ROWS, 128), jnp.int32,
                                 sharding=one_chip)
    ids = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((batch, 128), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda t, i, v: cscatter(t, i, v, kind="xor", interpret=False),
        donate_argnums=0).lower(table, ids, vals).compile()
    (call,) = [line for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line]
    assert re.match(r"\s*(ROOT )?%cscatter\.\d+ = ", call)
    assert "output_to_operand_aliasing={{}: (5, {})}" in call
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 25


def test_xor_store_commit_compiles_for_v5e(topo, no_persistent_cache,
                                           monkeypatch):
    """The partitioned XOR store's commit tick compiles for four described
    v5e chips with the kernel, its collectives are the scheduled route
    (CC021): an all-gather, no all-reduce. It holds no table-sized
    temporary: the settled lines are updated in place."""
    from repro.analysis import placement
    from repro.apps.sharded import mesh_spmd
    from repro.core.ccache import deferred_stages_of
    from repro.core.defer_schedule import DeferSchedule
    from repro.core.merge_functions import BITWISE_XOR
    from repro.launch import hlo_cost
    from repro.serve import KVConfig, ShardedKV, serving_plan

    S = 4
    mesh = Mesh(np.asarray(topo.devices[:S]), ("shards",))
    plan = serving_plan(S, "all")
    levels = tuple(s.name for s in deferred_stages_of(plan, S,
                                                      merge_fn=BITWISE_XOR))
    shard = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
        "shards"))
    spmd = mesh_spmd(mesh)
    spmd_abstract = lambda fn, *args: jax.eval_shape(  # noqa: E731
        lambda *a: spmd(fn, *a), *args)
    store = ShardedKV(
        KVConfig(n_keys=1 << 28, cols=2, dtype=jnp.int32, merge=BITWISE_XOR,
                 engine="kernel", partitioned=True),
        S, spmd_abstract, plan=plan, schedule=DeferSchedule.fixed(8, levels))
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((S,) + s.shape, s.dtype,
                                       sharding=shard),
        store.tick_arg_specs(BATCH))
    assert args[0].shape == (S, 1 << 20, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = spmd.lower(store.raw_tick_fn(store.n_deferred), *args,
                          donate=store.donate_argnums).compile()
    hlo = compiled.as_text()
    assert "cscatter" in hlo and "all-gather" in hlo
    assert "all-reduce" not in hlo
    walk = hlo_cost.analyze_hlo(
        hlo, level_sizes=tuple(lv.size for lv in plan.levels),
        level_names=tuple(lv.name for lv in plan.levels))
    assert placement.check_commit_walk(walk, store.scheduled_manifest(),
                                       "xor:commit") == []
    table_bytes = (1 << 20) * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < table_bytes // 8
